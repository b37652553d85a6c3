"""The architecture runner (``runners/serve_arch.py``) and what PR 26
added beside it: ``run.py`` end to end on the CPU at toy widths for
the new cell (files under ``tests/toy``, spec ``BENCHMARK-arch.json``)
as it is and with an expert layer that drops, ``archs/olmoe.py``'s
refusal of keys it does not map and its counts, the four expert-layer
readers on a recorded counter set, and the burst traffic file that
waits for its cell (PERF.md section 7)."""
import importlib
import json
import os
import subprocess
import sys

import pytest
from test_run_cpu import DRIVER as _DRIVER

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "toy")

# test_run_cpu.py's driver (it replaces run.check_devices and points
# run.py at tests/toy), with this file's spec in place of its own
DRIVER = _DRIVER.replace('"/BENCHMARK.json"', '"/BENCHMARK-arch.json"')
assert DRIVER != _DRIVER


def run_cell(cell, tmp_path, driver=DRIVER):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    code = driver.format(bench=os.path.join(ROOT, "benchmarks"), root=ROOT,
                         toy=TOY)
    out = subprocess.run(
        [sys.executable, "-c", code, "--workload", cell, "--seed",
         "2147483659", "--seconds", "4", "--trace", "0"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


def test_new_cell_runs_on_cpu_at_toy_width(tmp_path):
    line, log = run_cell("serve-moe-decode-open", tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {
        "serve_tokens_per_s", "setup_s", "serve_latency_p50_s",
        "serve_latency_p90_s"}
    assert line["device"]["platform"] == "cpu"   # never a device metric
    # float32 at toy width: the program routes as the reference does,
    # and its logits are the reference's to float32 rounding
    assert "expert sets differ in 0.000%" in log
    assert ("'nothing_dropped': True, 'every_token_routed': True, "
            "'expert_layers': True, 'block_logits': True") in log


# the same cell with the expert layers on the capacity path at 1 x:
# assignments past an expert's capacity are dropped
DROPPING = DRIVER.replace(
    "import run\n", "import run\n"
    "from archs import olmoe\n"
    "_cfg = olmoe.transformer_config\n"
    "olmoe.transformer_config = lambda conf, **kw: _cfg(\n"
    "    conf, **dict(kw, moe_capacity=1.0))\n", 1)
assert DROPPING != DRIVER


def test_a_dropping_expert_layer_is_not_correct(tmp_path):
    """``correct`` rests on the engine's counters too: a served-token
    margin alone passed this variant on the chip (PR 26, chip run 4)."""
    line, log = run_cell("serve-moe-decode-open", tmp_path, DROPPING)
    assert line["correct"] is False
    assert "'every_token_routed': False" in log


def test_the_real_spec_and_toy_spec_name_the_same_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TOY, "BENCHMARK-arch.json")) as f:
        toy = json.load(f)
    cells = {w["name"]: w for w in real["workloads"]}
    for w in toy["workloads"]:
        assert cells[w["name"]]["traffic"] == w["traffic"]
    for m in toy["per_layer"]:
        importlib.import_module(f"layer_metrics.{m['name']}")


def test_the_burst_trace_fits_the_generator():
    """``chat-burst-open`` has no cell yet (its latencies spread by more
    than half their bound, PERF.md section 7); the file stays ready:
    94 requests in 45 s, most of them inside a few bursts."""
    from generators import open_trace
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "chat-burst-open.json")) as f:
        traffic = json.load(f)
    plan = open_trace.schedule(traffic, 7, 45.0, 128)
    due = sorted(r["due"] for r in plan["requests"] if r["window"])
    assert len(due) == 94 and 0.0 <= due[0] and due[-1] < 45.0
    gaps = [b - a for a, b in zip(due, due[1:])]
    assert sum(g < 0.05 for g in gaps) > 40 and max(gaps) > 4.0


def olmoe_conf():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "olmoe-1b-7b-serve-d6.json")) as f:
        return json.load(f)


def test_arch_refuses_a_key_it_does_not_map():
    from archs import olmoe
    conf = olmoe_conf()
    cfg = olmoe.transformer_config(conf, max_len=4096)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.mlp_dim) == (64, 8, 1024)
    assert cfg.qk_norm and cfg.moe_gated and not cfg.moe_norm_topk
    assert cfg.moe_capacity <= 0 and cfg.norm_eps == 1e-5
    with pytest.raises(ValueError, match="shared_expert"):
        olmoe.transformer_config(dict(conf, shared_expert_size=1024),
                                 max_len=4096)
    with pytest.raises(ValueError, match="hidden_act"):
        olmoe.transformer_config(dict(conf, hidden_act="gelu"), max_len=4096)


def test_arch_counts_are_the_configuration_files():
    from archs import olmoe

    from edl_tpu.models.transformer import param_count
    conf = olmoe_conf()
    assert olmoe.param_count(conf) == conf["memory"]["parameters"]
    assert olmoe.param_count(conf) == param_count(
        olmoe.transformer_config(conf, max_len=4096))
    assert olmoe.expert_params(conf) * 2 == 12_582_912      # 12.58 MB
    # 8 live slots, about 41 experts a layer: 3.1 GB of experts
    need = olmoe.decode_step_min_bytes(conf, 41.0, 8 * 400)
    assert 3.5e9 < need < 4.0e9


# a 45 s window of the expert cell: 400 ticks x 4 token steps x 6 layers
COUNTERS = {
    "window_s": 45.0, "steps_per_sync": 4, "moe_assignments": 1_200_000,
    "moe_decode_layer_steps": 9600, "moe_decode_experts_touched": 432_000,
    "moe_prefill_groups": 300, "moe_prefill_experts_touched": 18_000,
    "moe_prefill_max_load_sum": 540.0,
    # sampled every 100 ms: 9 live slots, 6 while the trace ran (2-6.5 s)
    "active_slots_samples": [9] * 20 + [6] * 45 + [9] * 385,
    "mean_context_tokens": 400.0,
    # the engine's counters between the trace's edges (serve_arch.py):
    # 40 step programs x 4 token steps x 6 layers touched 36 experts
    "trace_span_counters": {
        "moe_assignments": 100_000, "moe_decode_layer_steps": 960,
        "moe_decode_experts_touched": 34_560,
        "moe_prefill_experts_touched": 2_000},
}
TRACE = {"window_s": 4.5,
         "ops": {"ragged-dot-none.7_bf16_96_1024_": 0.9,
                 "ragged-dot-metadata.7_s32_65_": 0.5,
                 "fusion.12_bf16_96_2048_": 0.3},
         "modules": {"jit__step_impl": {"count": 40, "total_s": 3.2}}}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def ctx(counters, trace):
    return {"counters": counters, "trace": trace, "peak": PEAK,
            "conf": olmoe_conf()}


def reader(name):
    return importlib.import_module(f"layer_metrics.{name}").read


def test_expert_readers_on_a_recorded_counter_set():
    from archs import olmoe
    c = ctx(dict(COUNTERS), TRACE)
    assert reader("moe_experts_touched_mean")(c) == pytest.approx(45.0)
    assert reader("moe_prefill_load_imbalance")(c) == pytest.approx(1.8)
    conf = olmoe_conf()
    # the step's experts are the traced span's own (36), the live keys
    # and values the window's (8.7 slots x 400 tokens)
    need = olmoe.decode_step_min_bytes(conf, 36.0, 8.7 * 400.0)
    assert reader("moe_decode_step_roofline")(c) == pytest.approx(
        100.0 * need / 819e9 / (3.2 / 40 / 4))
    # the pairs and the expert weight sets counted in the span, against
    # the 0.9 s of grouped matmuls (metadata op out)
    flops, nbytes = olmoe.expert_matmul_min(conf, 100_000, 34_560 + 2_000)
    assert reader("moe_expert_matmul_roofline")(c) == pytest.approx(
        100.0 * max(flops / 197e12, nbytes / 819e9) / 0.9)
    for name in ("moe_decode_step_roofline", "moe_expert_matmul_roofline"):
        assert 0 < reader(name)(c) <= 100.0


@pytest.mark.parametrize("name", [
    "moe_experts_touched_mean", "moe_prefill_load_imbalance",
    "moe_expert_matmul_roofline", "moe_decode_step_roofline"])
def test_expert_readers_say_nothing_where_there_is_nothing(name):
    """The parent's engine has none of these counters, and an untraced
    run has no trace: the reader returns None and never raises."""
    old = {"window_s": 45.0, "steps_per_sync": 4, "moe_prefill_drops": 0}
    assert reader(name)(ctx(old, TRACE)) is None
    assert reader(name)(ctx(dict.fromkeys(COUNTERS, 0), TRACE)) is None
    if "roofline" in name:
        assert reader(name)(ctx(dict(COUNTERS), None)) is None
        # no counters from the trace's edges: nothing is modelled instead
        untapped = {k: v for k, v in COUNTERS.items()
                    if k != "trace_span_counters"}
        assert reader(name)(ctx(untapped, TRACE)) is None
