"""The state-space runner (``runners/serve_ssm.py``) and what PR 32
added beside it: ``run.py`` end to end on the CPU at toy widths for the
new cell (files under ``tests/toy``, spec ``BENCHMARK-ssm.json``) as it
is and with the residual multiplier left out,
``archs/granite_moe_hybrid.py``'s refusal of keys it does not map and
its counts against the configuration file, the three new readers on a
recorded counter set, and the traffic file against the generator."""
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
CELL = "serve-ssm-chat-open"
CONFIG = "granite-4.0-h-small-serve-ep2"

DRIVER = _DRIVER.replace('"/BENCHMARK.json"', '"/BENCHMARK-ssm.json"')
assert DRIVER != _DRIVER


def run_cell(tmp_path, driver=DRIVER):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    code = driver.format(bench=os.path.join(ROOT, "benchmarks"), root=ROOT,
                         toy=TOY)
    out = subprocess.run(
        [sys.executable, "-c", code, "--workload", CELL, "--seed",
         "2147483659", "--seconds", "4", "--trace", "0"],
        capture_output=True, text=True, timeout=1500, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


def test_new_cell_runs_on_cpu_at_toy_width(tmp_path):
    line, log = run_cell(tmp_path)
    assert line["correct"] is True and line["failed"] == 0, log[-3000:]
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {
        "serve_tokens_per_s", "setup_s", "serve_latency_p50_s",
        "serve_latency_p90_s"}
    assert line["device"]["platform"] == "cpu"   # never a device metric
    # float32 at toy width: the program routes as the reference does
    assert "expert sets differ in 0.000%" in log
    assert ("'nothing_dropped': True, 'every_token_routed': True, "
            "'held_pairs_recount': True, 'mixer_layers': True, "
            "'attention_layers': True, 'expert_layers': True, "
            "'routed_experts': True, 'carried_state': True, "
            "'block_logits': True, 'cache_logits': True") in log
    assert "pooled-equal True" in log and "prefix-hit 1" in log
    held = log.split("held pairs on the cold probe: the engine computed ")[1]
    computed, recount = held.split(", the host recounts ")
    assert int(computed) == int(recount.split(" ")[0]) > 0


# the same cell with the residual multiplier 0.22 left out of the PROGRAM
WRONG = DRIVER.replace(
    "import run\n", "import run\n"
    "from archs import granite_moe_hybrid\n"
    "_cfg = granite_moe_hybrid.transformer_config\n"
    "granite_moe_hybrid.transformer_config = lambda conf, **kw: _cfg(\n"
    "    conf, **dict(kw, residual_multiplier=1.0))\n", 1)
assert WRONG != DRIVER


def test_a_block_without_its_residual_multiplier_is_not_correct(tmp_path):
    line, log = run_cell(tmp_path, WRONG)
    assert line["correct"] is False
    assert "'block_logits': False" in log


def conf_of():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{CONFIG}.json")) as f:
        return json.load(f)


def test_the_real_spec_and_toy_spec_name_the_same_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TOY, "BENCHMARK-ssm.json")) as f:
        toy = json.load(f)
    cells = {w["name"]: w for w in real["workloads"]}
    assert cells[CELL]["config"] == CONFIG and cells[CELL]["chips"] == 1
    assert [w["name"] for w in toy["workloads"]] == [CELL]
    for w in toy["workloads"]:
        assert cells[w["name"]]["traffic"] == w["traffic"]
    listed = {m["name"] for m in real["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {m["name"] for m in toy["per_layer"]}
    for m in toy["per_layer"]:
        importlib.import_module(f"layer_metrics.{m['name']}")
    for m in real["per_layer"]:
        if m["name"].startswith("ssm_"):
            # a later cell with layer state shares the state counters
            # (PR 37's): this cell leads the list
            assert m["workloads"][0] == CELL
    assert sum(w["chips"] == 4 for w in real["workloads"]) == 1


def test_arch_maps_every_key_and_refuses_the_rest():
    import jax.numpy as jnp
    from archs import granite_moe_hybrid as arch
    conf = conf_of()
    cfg = arch.transformer_config(conf, max_len=4096)
    assert (cfg.embed_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (
        4096, 32, 8, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (128, 64, 128, 1, 4, 256)
    assert (cfg.ssm_inner, cfg.ssm_conv_dim) == (8192, 8448)
    assert (cfg.expert_dim, cfg.moe_shared_dim) == (768, 1536)
    assert (cfg.moe_experts, cfg.moe_held, cfg.moe_top_k) == (72, 36, 10)
    assert cfg.layer_attn == ("ssm",) * 5 + ("global",) + ("ssm",) * 4
    assert (cfg.embed_multiplier, cfg.residual_multiplier, cfg.attn_scale,
            cfg.logits_scaling) == (12.0, 0.22, 1 / 128, 16.0)
    assert not cfg.rope_global and cfg.tie_embeddings
    assert cfg.ssm_conv_bias and not cfg.ssm_proj_bias
    assert cfg.ssm_state_dtype == jnp.float32 and cfg.norm_eps == 1e-5
    with pytest.raises(ValueError, match="sliding_window"):
        arch.transformer_config(dict(conf, sliding_window=128), max_len=4096)
    with pytest.raises(ValueError, match="position_embedding_type"):
        arch.transformer_config(dict(conf, position_embedding_type="rope"),
                                max_len=4096)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        arch.transformer_config(dict(conf, tie_word_embeddings=False),
                                max_len=4096)


def test_the_file_keeps_every_published_number():
    """The catalog's copy of config.json, where this sandbox has it:
    every number under the same key, but for the three in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "granite-4.0-h-small")
    conf = conf_of()
    assert conf["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if conf.get(k) != v}
    assert differ == set(conf["reduced"]) == set(conf["reduced_from"])
    assert all(conf["reduced_from"][k] == entry["config"][k] for k in differ)


def test_arch_counts_are_the_configuration_files():
    from archs import granite_moe_hybrid as arch

    from edl_tpu.models.transformer import param_count
    conf = conf_of()
    assert arch.param_count(conf) == conf["memory"]["parameters"]
    assert arch.param_count(conf) == 4_757_211_776 == param_count(
        arch.transformer_config(conf, max_len=4096))
    assert arch.mamba_params(conf) == 102_286_976
    assert arch.attention_params(conf) == 41_943_040
    assert arch.expert_params(conf) == 9_437_184
    assert (arch.mamba_layers(conf), arch.attention_layers(conf)) == (9, 1)
    assert arch.kv_bytes_per_token(conf) == 4096       # one attention layer
    assert arch.state_bytes_per_slot(conf) == 9 * (4 * 2**20 + 3 * 8448 * 2)
    # the issue's expectation: 2.72 GB whatever the batch; at 16 live
    # slots 36 x (1 - (62/72)^16) = 32.7 held experts a layer, 6.2 GB
    assert 2.70e9 < arch.decode_step_min_bytes(conf, 0.0, 0.0) < 2.75e9
    need = arch.decode_step_min_bytes(conf, 32.7, 0.0, live_slots=16)
    assert 9.9e9 < need < 10.3e9
    flops, nbytes = arch.ssm_step_min(conf, 1.0)
    assert flops == 6 * 2**20 and 8 * 2**20 < nbytes < 8.1 * 2**20


# a 45 s window of the cell: 800 ticks x 4 token steps, 14 of 32 live
COUNTERS = {
    "window_s": 45.0, "steps_per_sync": 4,
    "ssm_state_steps": 403_200, "ssm_state_steps_run": 403_200,
    "ssm_prefill_positions": 60_000, "ssm_prefill_positions_pad": 9_000,
    "trace_span_counters": {"ssm_state_steps": 36_000},
}
TRACE = {"window_s": 4.0,
         "ops": {"ssm_step.7_f32_32_128_64_128_": 0.5,
                 "decode_attend.1_bf16_32_8_16_128_": 0.1},
         "modules": {"jit__step_impl": {"count": 70, "total_s": 3.6}}}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["ssm_step_roofline", "ssm_state_live_share", "ssm_prefill_pad_share"]


def ctx(counters, trace):
    return {"counters": counters, "trace": trace, "peak": PEAK,
            "conf": conf_of()}


def reader(name):
    return importlib.import_module(f"layer_metrics.{name}").read


def test_new_readers_on_a_recorded_counter_set():
    from archs import granite_moe_hybrid as arch
    c = ctx(dict(COUNTERS), TRACE)
    assert reader("ssm_state_live_share")(c) == pytest.approx(100.0)
    einsum = dict(COUNTERS, ssm_state_steps_run=921_600)     # all 32 slots
    assert reader("ssm_state_live_share")(ctx(einsum, TRACE)) == (
        pytest.approx(43.75))
    assert reader("ssm_prefill_pad_share")(c) == pytest.approx(15.0)
    # 36,000 live (slot, step, layer) states of 4 MiB read and written
    # in the span, against the 0.5 s of the ssm_step kernel alone
    flops, nbytes = arch.ssm_step_min(conf_of(), 36_000)
    assert nbytes / 819e9 > flops / 197e12              # memory bound
    assert reader("ssm_step_roofline")(c) == pytest.approx(
        100.0 * (nbytes / 819e9) / 0.5)
    assert 0 < reader("ssm_step_roofline")(c) <= 100.0


@pytest.mark.parametrize("name", NEW)
def test_new_readers_say_nothing_where_there_is_nothing(name):
    """The parent's engine has none of these counters, and an untraced
    run has no trace: the reader returns None and never raises."""
    old = {"window_s": 45.0, "steps_per_sync": 4, "moe_prefill_drops": 0,
           "moe_assignments": 7}
    assert reader(name)(ctx(old, TRACE)) is None
    zeroed = {k: ({} if isinstance(v, dict) else 0)
              for k, v in COUNTERS.items()}
    assert reader(name)(ctx(zeroed, TRACE)) is None
    if "roofline" in name:
        assert reader(name)(ctx(dict(COUNTERS), None)) is None
        untapped = {k: v for k, v in COUNTERS.items()
                    if k != "trace_span_counters"}
        assert reader(name)(ctx(untapped, TRACE)) is None
        no_kernel = dict(TRACE, ops={"decode_attend.1": 0.5})
        assert reader(name)(ctx(dict(COUNTERS), no_kernel)) is None


def test_the_traffic_fits_the_engine_and_states_its_rate():
    from generators import open_trace
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "ssm-chat-open.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    conf = conf_of()
    assert traffic["generator"] == "open_trace" and traffic["loop"] == "open"
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32,
        "max": 2048}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32,
        "max": 1024}
    assert traffic["probe_tokens"] == 800 and not traffic["shared_prefix"]
    assert (traffic["warm_seconds"], traffic["drain_seconds"]) == (10.0, 30.0)
    shapes = open_trace.shapes(traffic, float(seconds), 16)
    assert shapes["max_total"] <= conf["run"]["max_len"] == 4096
    plan = open_trace.schedule(traffic, 2147483659, float(seconds),
                               conf["vocab_size"])
    n = round(traffic["rate_per_s"] * seconds)
    assert plan["offered"]["requests"] == n
    assert "0.7 of" in traffic["rate_note"]
