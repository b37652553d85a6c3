"""The latent runner (``runners/serve_latent.py``) and what PR 37 added
beside it: ``run.py`` end to end on the CPU at toy widths for the new
cell (files under ``tests/toy``, spec ``BENCHMARK-latent.json``) as it
is and with the delta correction left out of the program,
``archs/kimi_linear.py``'s refusal of keys it does not map and its
counts against the configuration file, the three new readers on a
recorded counter set, and the traffic file against the generator and
against the mixture its list is made from."""
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
CELL = "serve-latent-reason-open"
CONFIG = "kimi-linear-48b-a3b-serve-ep4"

DRIVER = _DRIVER.replace('"/BENCHMARK.json"', '"/BENCHMARK-latent.json"')
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
    assert ("'served_margin': True, 'pooled_margin': True, "
            "'nothing_dropped': True, 'every_token_routed': True, "
            "'held_pairs_recount': True, 'mixer_layers': True, "
            "'attention_layers': True, 'absorbed_attention': True, "
            "'expert_layers': True, 'routed_experts': True, "
            "'carried_state': True, 'block_logits': True, "
            "'cache_logits': True") in log
    assert "pooled-equal True" in log and "prefix-hit 1" in log
    held = log.split("held pairs on the cold probe: the engine computed ")[1]
    computed, recount = held.split(", the host recounts ")
    assert int(computed) == int(recount.split(" ")[0]) > 0


# the same cell with the delta correction left out of the PROGRAM's
# one-token update (u = v: a plain gated linear attention)
WRONG = DRIVER.replace(
    "import run\n", "import run\n"
    "import jax.numpy as jnp\n"
    "from edl_tpu.ops import kda\n"
    "def _no_delta(S, k, v, g, beta):\n"
    "    S = S * jnp.exp(g)[..., None]\n"
    "    return S + (beta[..., None] * k)[..., None] * v[:, :, None, :]\n"
    "kda._update = _no_delta\n", 1)
assert WRONG != DRIVER


def test_a_step_without_the_delta_correction_is_not_correct(tmp_path):
    line, log = run_cell(tmp_path, WRONG)
    assert line["correct"] is False
    assert "'carried_state': False" in log and "'cache_logits': False" in log
    assert "'mixer_layers': True" in log       # the chunked form is right


def conf_of():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{CONFIG}.json")) as f:
        return json.load(f)


def test_the_real_spec_and_toy_spec_name_the_same_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TOY, "BENCHMARK-latent.json")) as f:
        toy = json.load(f)
    cells = {w["name"]: w for w in real["workloads"]}
    assert cells[CELL]["config"] == CONFIG and cells[CELL]["chips"] == 1
    for w in toy["workloads"]:
        assert cells[w["name"]]["traffic"] == w["traffic"]
    listed = {m["name"] for m in real["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {m["name"] for m in toy["per_layer"]}
    for m in toy["per_layer"]:
        importlib.import_module(f"layer_metrics.{m['name']}")
    for m in real["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
    for m in real["end_to_end"]:
        assert m["name"] == "train_tokens_per_s_per_chip" or CELL in m.get(
            "workloads", [CELL])
    assert [w["name"] for w in toy["workloads"]] == [CELL]
    assert sum(w["chips"] == 4 for w in real["workloads"]) == 1


def test_arch_maps_every_key_and_refuses_the_rest():
    import jax.numpy as jnp
    from archs import kimi_linear as arch
    conf = conf_of()
    cfg = arch.transformer_config(conf, max_len=32768)
    assert (cfg.embed_dim, cfg.num_heads, cfg.mlp_dim, cfg.vocab_size) == (
        2304, 32, 9216, 40960)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv, cfg.kda_chunk) == (
        32, 128, 4, 64)
    assert (cfg.mla_rank, cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim,
            cfg.mla_rope) == (512, 128, 64, 128, False)
    assert (cfg.mla_width, cfg.mla_row) == (576, 640)
    assert (cfg.expert_dim, cfg.moe_shared_dim) == (1024, 1024)
    assert (cfg.moe_experts, cfg.moe_held, cfg.moe_top_k) == (256, 64, 8)
    assert (cfg.moe_router, cfg.moe_select_bias, cfg.moe_norm_topk,
            cfg.moe_routed_scale) == ("sigmoid", True, True, 2.446)
    assert cfg.layer_attn == ("kda", "kda", "kda", "latent") * 2
    assert cfg.layer_mlp == ("dense",) + ("sparse",) * 7
    assert cfg.kda_state_dtype == jnp.float32 and cfg.norm_eps == 1e-5
    assert not cfg.tie_embeddings
    with pytest.raises(ValueError, match="sliding_window"):
        arch.transformer_config(dict(conf, sliding_window=128), max_len=4096)
    with pytest.raises(ValueError, match="q_lora_rank"):
        arch.transformer_config(dict(conf, q_lora_rank=1536), max_len=4096)
    with pytest.raises(ValueError, match="topk_group"):
        arch.transformer_config(dict(conf, topk_group=2), max_len=4096)
    lin = dict(conf["linear_attn_config"], kda_layers=[1, 2, 3])
    with pytest.raises(ValueError, match="neither kda nor full"):
        arch.transformer_config(dict(conf, linear_attn_config=lin),
                                max_len=4096)
    # the published flag maps: rotation on is another model, not refused
    assert arch.transformer_config(dict(conf, mla_use_nope=False),
                                   max_len=4096).mla_rope


def test_the_file_keeps_every_published_number():
    """The catalog's copy of config.json, where this sandbox has it:
    every number under the same key, but for the three in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Kimi-Linear-48B-A3B-Instruct")
    conf = conf_of()
    assert conf["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if conf.get(k) != v}
    assert differ == set(conf["reduced"]) == set(conf["reduced_from"])
    assert all(conf["reduced_from"][k] == entry["config"][k] for k in differ)


def test_arch_counts_are_the_configuration_files():
    from archs import kimi_linear as arch

    from edl_tpu.models.transformer import param_count
    conf = conf_of()
    assert arch.param_count(conf) == conf["memory"]["parameters"]
    assert arch.param_count(conf) == 3_772_368_832 == param_count(
        arch.transformer_config(conf, max_len=32768))
    assert arch.kda_params(conf) == 39_514_272
    assert arch.mla_matmul_params(conf) == 29_114_368
    assert arch.expert_params(conf) == 7_077_888
    assert (arch.kda_layers(conf), arch.latent_layers(conf),
            arch.sparse_layers(conf)) == (6, 2, 7)
    assert arch.kv_bytes_per_token(conf) == 2 * 1152    # two latent layers
    assert arch.state_bytes_per_slot(conf) == 6 * (2 * 2**20 + 3 * 12288 * 2)
    # the issue's expectation: 1.1 GB of shared weights whatever the
    # batch; at 20 live slots 64 x (1 - (248/256)^20) = 30 held experts
    assert 1.00e9 < arch.decode_step_min_bytes(conf, 0.0, 0.0) < 1.05e9
    need = arch.decode_step_min_bytes(conf, 30.0, 20 * 1500.0, live_slots=20)
    assert 4.5e9 < need < 4.7e9
    flops, nbytes = arch.kda_step_min(conf, 1.0)
    assert flops == 8 * 2**19 and 4 * 2**20 < nbytes < 4.1 * 2**20
    flops, nbytes = arch.latent_attention_min(conf, 1000.0, 1.0)
    assert nbytes == 1000 * 1152 + (576 + 2 * 32 * 576) * 2
    assert 55 < flops / nbytes < 65                     # FLOPs a byte


# a 45 s window of the cell: 1000 ticks x 4 token steps, 18 of 32 live
COUNTERS = {
    "window_s": 45.0, "steps_per_sync": 4,
    "ssm_state_steps": 432_000, "ssm_state_steps_run": 432_000,
    "latent_tokens_live": 230_400_000, "latent_tokens_read": 276_480_000,
    "latent_prefill_rows_live": 2_647_040, "latent_prefill_rows_read": 3_000_320,
    "trace_span_counters": {"ssm_state_steps": 38_400,
                            "latent_tokens_live": 20_480_000},
}
TRACE = {"window_s": 4.0,
         "ops": {"kda_step.7_f32_32_32_128_128_": 0.4,
                 "latent_attend.3_bf16_32_32_640_": 0.05,
                 "latent_append.2_bf16_32_32768_640_": 0.002,
                 "decode_attend.1_bf16_32_8_16_128_": 0.1},
         "modules": {"jit__step_impl": {"count": 90, "total_s": 3.6}}}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["kda_step_roofline", "latent_attention_roofline",
       "kv_latent_read_ratio", "latent_prefill_read_ratio"]


def ctx(counters, trace):
    return {"counters": counters, "trace": trace, "peak": PEAK,
            "conf": conf_of()}


def reader(name):
    return importlib.import_module(f"layer_metrics.{name}").read


def test_new_readers_on_a_recorded_counter_set():
    from archs import kimi_linear as arch
    c = ctx(dict(COUNTERS), TRACE)
    assert reader("kv_latent_read_ratio")(c) == pytest.approx(1.2)
    # the window's counts of PERF.md section 5 (my chip run 3, PR 38)
    assert reader("latent_prefill_read_ratio")(c) == pytest.approx(
        1.1335, abs=1e-4)
    # 38,400 live (slot, step, layer) states of 2 MiB read and written
    # in the span, against the 0.4 s of the kda_step kernel alone
    flops, nbytes = arch.kda_step_min(conf_of(), 38_400)
    assert nbytes / 819e9 > flops / 197e12              # memory bound
    assert reader("kda_step_roofline")(c) == pytest.approx(
        100.0 * (nbytes / 819e9) / 0.4)
    assert 0 < reader("kda_step_roofline")(c) <= 100.0
    # 20,480,000 live positions read by 38,400 x 2 / 6 calls, against
    # the 0.052 s of the two latent kernels
    flops, nbytes = arch.latent_attention_min(conf_of(), 20_480_000, 12_800)
    assert nbytes / 819e9 > flops / 197e12              # memory bound
    assert reader("latent_attention_roofline")(c) == pytest.approx(
        100.0 * (nbytes / 819e9) / 0.052)
    assert 0 < reader("latent_attention_roofline")(c) <= 100.0


@pytest.mark.parametrize("name", NEW)
def test_new_readers_say_nothing_where_there_is_nothing(name):
    """The parent's engine has none of these counters, and an untraced
    run has no trace: the reader returns None and never raises; nor
    under another cell's configuration."""
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
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               "granite-4.0-h-small-serve-ep2.json")) as f:
            other = dict(ctx(dict(COUNTERS), TRACE), conf=json.load(f))
        assert reader(name)(other) is None


def test_the_traffic_fits_the_engine_and_states_its_rate():
    from generators import open_trace
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "reason-long-open.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    conf = conf_of()
    n = round(traffic["rate_per_s"] * seconds)
    assert traffic["generator"] == "open_trace" and traffic["loop"] == "open"
    assert traffic["trace_seed"] == 20260930
    assert traffic["prompt_tokens"]["dist"] == "mixture"
    assert [p["share"] for p in traffic["prompt_tokens"]["parts"]] == [
        0.95, 0.05]
    values = sorted(int(v) for v in open_trace.cycle(traffic,
                                                     float(seconds))[0])
    # the list the file held until PR 40, value for value: its ends, its
    # three documents and its sum
    assert values[:3] == [128, 169, 204] and values[-4:] == [
        4096, 8345, 12288, 18094] and sum(values) == 91145
    long = [v for v in values if v >= 8192]
    assert len(long) == n - round(0.95 * n) and len(long) < 0.1 * n
    assert min(values) >= 128 and max(values) <= 24576
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 768, "sigma": 0.5, "min": 256,
        "max": 1536}
    assert traffic["probe_tokens"] == 1100 and not traffic["shared_prefix"]
    assert (traffic["warm_seconds"], traffic["drain_seconds"]) == (20.0, 30.0)
    shapes = open_trace.shapes(traffic, float(seconds),
                               conf["run"]["kv_block"])
    assert shapes["max_total"] <= conf["run"]["max_len"] == 32768
    plan = open_trace.schedule(traffic, 2147483659, float(seconds),
                               conf["vocab_size"])
    assert plan["offered"]["requests"] == n
    assert "of the knee" in traffic["rate_note"] or "0.7 of" in traffic[
        "rate_note"]
    # the toy copy lists its own
    with open(os.path.join(TOY, "traffic", "reason-long-open.json")) as f:
        toy = json.load(f)
    assert len(toy["prompt_tokens"]["values"]) == round(
        toy["rate_per_s"] * 4)
