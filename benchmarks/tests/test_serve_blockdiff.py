"""The block-diffusion runner (``runners/serve_blockdiff.py``) and what
PR 48 added beside it: ``run.py`` end to end on the CPU at toy widths
for the new cell (files under ``tests/toy``, spec
``BENCHMARK-blockdiff.json``) as it is and with a commit that keeps a
denoise pass's K/V, ``archs/sdar_moe.py``'s refusal of keys it does not
map and its counts against the configuration file, the generator on the
cell's fixed answer lengths, and the five new readers on a recorded
counter set."""
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
CELL = "serve-blockdiff-chat-open"

DRIVER = _DRIVER.replace('"/BENCHMARK.json"', '"/BENCHMARK-blockdiff.json"')
assert DRIVER != _DRIVER


def run_cell(tmp_path, driver=DRIVER, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    code = driver.format(bench=os.path.join(ROOT, "benchmarks"), root=ROOT,
                         toy=TOY)
    out = subprocess.run(
        [sys.executable, "-c", code, "--workload", CELL, "--seed",
         "2147483659", "--seconds", "4", "--trace", str(trace)],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


def test_new_cell_runs_on_cpu_at_toy_width(tmp_path):
    line, log = run_cell(tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {
        "serve_tokens_per_s", "setup_s", "serve_latency_p50_s",
        "serve_latency_p90_s"}
    assert line["device"]["platform"] == "cpu"   # never a device metric
    # float32 at toy width: the engine unmasks what the reference would
    # (the cold probe alone, then the burst served together)
    assert log.count("token shortfall max 0.0000, ") == 2
    assert log.count("position shortfall max 0.0000, ") == 2
    assert "8 requests served together, 288 unmaskings" in log
    assert ("'served_tokens': True, 'served_positions': True, "
            "'together_tokens': True, 'together_positions': True, "
            "'together_live': True, "
            "'cache_logits': True, 'expert_layers': True, "
            "'probe_counted': True, 'nothing_dropped': True, "
            "'every_token_routed': True") in log
    assert "compiles in window []" in log


# the same cell on an engine whose commit advances the index WITHOUT its
# pass: the rows keep the K/V of the last denoise pass (one position of
# every block was still the mask token there)
KEEPS_DENOISE_KV = DRIVER.replace(
    "import run\n", "import run\n"
    "import jax, jax.numpy as jnp\n"
    "from edl_tpu.serving import engine as E\n"
    "_fwd = E.ContinuousBatcher._pass_forward\n"
    "def _kept(self, params, cache, tok, masked, on):\n"
    "    logits, mut = _fwd(self, params, cache, tok, masked, on)\n"
    "    commit = ~masked.any(axis=1)\n"
    "    keep = lambda new, old: (new if new.ndim == 1 else jnp.where(\n"
    "        commit.reshape((-1,) + (1,) * (new.ndim - 1)), old, new))\n"
    "    mut = dict(mut, cache=jax.tree.map(keep, mut['cache'], cache))\n"
    "    return logits, mut\n"
    "E.ContinuousBatcher._pass_forward = _kept\n", 1)
assert KEEPS_DENOISE_KV != DRIVER


def test_kv_kept_from_a_denoise_pass_is_not_correct(tmp_path):
    line, log = run_cell(tmp_path, KEEPS_DENOISE_KV)
    assert line["correct"] is False
    # by check (a), and by the tokens of the requests served together
    assert "'cache_logits': False" in log
    assert "'together_tokens': False" in log


def test_the_real_spec_and_toy_spec_name_the_same_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TOY, "BENCHMARK-blockdiff.json")) as f:
        toy = json.load(f)
    cells = {w["name"]: w for w in real["workloads"]}
    for w in toy["workloads"]:
        assert cells[w["name"]]["traffic"] == w["traffic"]
        assert cells[w["name"]]["chips"] == 1
    listed = {m["name"] for m in real["per_layer"]
              if CELL in m.get("workloads", [])}
    for m in toy["per_layer"]:
        importlib.import_module(f"layer_metrics.{m['name']}")
        assert m["name"] in listed
    # the cell reports setup_s, the three serve metrics and nothing of
    # the share readers whose count of work is a token step's
    assert not listed & {"moe_decode_step_roofline",
                         "moe_expert_matmul_roofline",
                         "decode_step_roofline"}
    for m in real["end_to_end"]:
        assert CELL in m.get("workloads", [CELL]) or \
            m["name"] == "train_tokens_per_s_per_chip"


def real_traffic():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "blockdiff-fixed-open.json")) as f:
        return json.load(f)


def test_the_traffic_is_fixed_lengths_in_multiples_of_the_block():
    from generators import open_trace
    traffic = real_traffic()
    plan = open_trace.schedule(traffic, 2147489001, 45.0, 151936)
    window = [r for r in plan["requests"] if r["window"]]
    n = len(window)
    assert n == round(traffic["rate_per_s"] * 45) >= 50
    outs = sorted(r["max_new"] for r in window)
    assert set(outs) == {128, 256, 512}
    assert outs.count(128) == round(0.3 * n)
    assert outs.count(256) == round(0.5 * n)
    lens = [len(r["prompt"]) for r in window]
    assert min(lens) >= 32 and max(lens) <= 2048
    assert all(1 <= t < 151936 for r in window[:3] for t in r["prompt"])
    # the same trace whatever the seed; other ids
    again = open_trace.schedule(traffic, 7, 45.0, 151936)["requests"]
    assert [(len(r["prompt"]), r["max_new"], r["due"]) for r in again] == [
        (len(r["prompt"]), r["max_new"], r["due"])
        for r in plan["requests"]]
    assert traffic["probe_tokens"] % 4 == 3      # a tail of three


def sdar_conf():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sdar-30b-a3b-chat-serve-d6.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_catalogs_but_for_depth():
    conf = sdar_conf()
    cat = os.path.join("/opt/skills/guides/model-configs",
                       "architectures.jsonl")
    if not os.path.exists(cat):
        pytest.skip("no catalog here")
    with open(cat) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    assert conf["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if conf.get(k) != v}
    assert differ == {"num_hidden_layers"} == set(conf["reduced"])
    assert conf["reduced_from"] == {"num_hidden_layers": 48}
    assert conf["run"]["steps_per_sync"] % 5 == 0
    assert conf["run"]["mask_token_id"] < conf["vocab_size"]


def test_arch_refuses_a_key_it_does_not_map_and_counts_the_file():
    from archs import sdar_moe

    from edl_tpu.models.transformer import param_count
    conf = sdar_conf()
    cfg = sdar_moe.transformer_config(conf, max_len=4096)
    assert (cfg.moe_experts, cfg.moe_top_k, cfg.expert_dim) == (128, 8, 768)
    assert cfg.qk_norm and cfg.qk_norm_per_head and cfg.moe_norm_topk
    assert cfg.block_length == 4 and cfg.moe_capacity <= 0
    with pytest.raises(ValueError, match="shared_expert"):
        sdar_moe.transformer_config(dict(conf, shared_expert_size=1024),
                                    max_len=4096)
    with pytest.raises(ValueError, match="use_sliding_window"):
        sdar_moe.transformer_config(dict(conf, use_sliding_window=True),
                                    max_len=4096)
    assert sdar_moe.param_count(conf) == 4_361_055_744
    assert sdar_moe.param_count(conf) == conf["memory"]["parameters"]
    assert sdar_moe.param_count(conf) == param_count(cfg)
    assert sdar_moe.expert_params(conf) * 2 == 9_437_184       # 9.4 MB
    # a pass of 10 live slots: 40 positions x 2 x 0.65 G active weights
    # (6 x (19.1 M shared + 8 x 4.7 M experts) + the head's 311 M)
    flops = sdar_moe.pass_flops(conf, 10, 10 * 304)
    assert 40 * 2 * 0.65e9 < flops < 40 * 2 * 0.68e9
    # attention: 300 rows x 4 KiB... 2 KiB a row a layer, six layers
    nbytes = sdar_moe.block_attend_bytes(conf, 10, 10 * 304)
    assert nbytes == 6 * (3040 * 2048 + 10 * (4 * 2048 + 2 * 4 * 32 * 128 * 2))


# a 45 s window of the cell: 3,200 passes x 6 layers; 9 slots live
COUNTERS = {
    "window_s": 45.0, "steps_per_sync": 10,
    "blockdiff_passes": 3200, "blockdiff_slot_passes": 28_000,
    "blockdiff_blocks_committed": 5_600, "blockdiff_tokens_unmasked": 22_300,
    "blockdiff_given_tokens": 100, "blockdiff_tokens_delivered": 22_300,
    "decode_kv_tokens_live": 14_000_000,
    "moe_decode_layer_steps": 19_200, "moe_decode_experts_touched": 2_200_000,
    "trace_span_counters": {
        "blockdiff_passes": 300, "blockdiff_slot_passes": 2_700,
        "blockdiff_blocks_committed": 540, "decode_kv_tokens_live": 1_350_000,
        "moe_decode_layer_steps": 1_800,
        "moe_decode_experts_touched": 207_000,
        "kv_prefill_tokens": 2_400, "kv_prefill_tokens_skipped": 0},
}
TRACE = {"window_s": 4.0, "busy_s": 3.9,
         "ops": {"block_attend.7": 0.20, "block_append.3": 0.04,
                 "moe_decode_gmm.5_bf16_": 2.4, "decode_attend.2": 9.0},
         "modules": {"jit__pass_impl": {"count": 30, "total_s": 3.6},
                     "jit_prefill": {"count": 7, "total_s": 0.2}}}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["blockdiff_tokens_per_slot_pass", "blockdiff_commit_pass_share",
       "block_attend_roofline", "blockdiff_pass_roofline",
       "blockdiff_step_mfu"]


def ctx(counters, trace):
    return {"counters": counters, "trace": trace, "peak": PEAK,
            "conf": sdar_conf()}


def reader(name):
    return importlib.import_module(f"layer_metrics.{name}").read


def test_new_readers_on_a_recorded_counter_set():
    from archs import sdar_moe
    conf, c = sdar_conf(), ctx(dict(COUNTERS), TRACE)
    assert reader("blockdiff_tokens_per_slot_pass")(c) == pytest.approx(
        22_300 / 28_000)
    assert reader("blockdiff_commit_pass_share")(c) == pytest.approx(20.0)
    # the two kernels' 0.24 s (decode_attend is another kernel's)
    need = sdar_moe.block_attend_bytes(conf, 2_700, 1_350_000)
    assert reader("block_attend_roofline")(c) == pytest.approx(
        100.0 * need / 819e9 / 0.24)
    # a pass: 115 experts a layer, 4,500 rows held by the live slots
    need = sdar_moe.decode_step_min_bytes(conf, 115.0, 4_500.0)
    assert reader("blockdiff_pass_roofline")(c) == pytest.approx(
        100.0 * need / 819e9 / (3.6 / 30 / 10))
    flops = (sdar_moe.pass_flops(conf, 2_700, 1_350_000)
             + sdar_moe.prefill_flops(conf, 2_400))
    assert reader("blockdiff_step_mfu")(c) == pytest.approx(
        100.0 * flops / 197e12 / 3.9)
    for name in NEW[2:]:
        assert 0 < reader(name)(c) <= 100.0


@pytest.mark.parametrize("name", NEW)
def test_new_readers_say_nothing_where_there_is_nothing(name):
    """The parent's engine has none of these counters, and an untraced
    run has no trace: the reader returns None and never raises."""
    old = {"window_s": 45.0, "steps_per_sync": 4, "moe_prefill_drops": 0}
    assert reader(name)(ctx(old, TRACE)) is None
    assert reader(name)(ctx(dict.fromkeys(COUNTERS, 0), TRACE)) is None
    if "roofline" in name or "mfu" in name:
        assert reader(name)(ctx(dict(COUNTERS), None)) is None
        untapped = {k: v for k, v in COUNTERS.items()
                    if k != "trace_span_counters"}
        assert reader(name)(ctx(untapped, TRACE)) is None
        # a trace of another program: no kernel, no pass program
        other = {"window_s": 4.0, "busy_s": 0.0,
                 "ops": {"decode_attend.2": 1.0},
                 "modules": {"jit__step_impl": {"count": 4, "total_s": 1}}}
        assert reader(name)(ctx(dict(COUNTERS), other)) is None
