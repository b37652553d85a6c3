"""The hybrid runner (``runners/serve_hybrid.py``) and what PR 30 added
beside it: ``run.py`` end to end on the CPU at toy widths for the new
cell (files under ``tests/toy``, spec ``BENCHMARK-hybrid.json``) as it
is and with the gates' factor left out, ``archs/exaone_moe.py``'s refusal of keys
it does not map and its counts against the configuration file, the
three new readers on a recorded counter set, and the traffic file's
listed mixture."""
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
# the name PR 30 gave the cell (tests/ hold it in the metrics' lists); since
# PR 40 its traffic is the re-cut, twice the requests at 7 : 1 short to long
CELL = "serve-hybrid-mixed-open"
TRAFFIC = "mixed-length-open-v2"

DRIVER = _DRIVER.replace('"/BENCHMARK.json"', '"/BENCHMARK-hybrid.json"')
assert DRIVER != _DRIVER


def run_cell(tmp_path, driver=DRIVER):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    code = driver.format(bench=os.path.join(ROOT, "benchmarks"), root=ROOT,
                         toy=TOY)
    out = subprocess.run(
        [sys.executable, "-c", code, "--workload", CELL, "--seed",
         "2147483659", "--seconds", "4", "--trace", "0"],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
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
            "'held_pairs_recount': True, 'expert_layers': True, "
            "'held_experts': True, 'block_logits': True") in log
    # in float32 the engine's count and the host's recount are equal
    held = log.split("held pairs on the cold probe: the engine computed ")[1]
    computed, recount = held.split(", the host recounts ")
    assert int(computed) == int(recount.split(" ")[0]) > 0


# the same cell with the gates' factor 2.5 left out of the PROGRAM (the
# reference keeps the published one)
WRONG = DRIVER.replace(
    "import run\n", "import run\n"
    "from archs import exaone_moe\n"
    "_cfg = exaone_moe.transformer_config\n"
    "exaone_moe.transformer_config = lambda conf, **kw: _cfg(\n"
    "    conf, **dict(kw, moe_routed_scale=1.0))\n", 1)
assert WRONG != DRIVER


def test_gates_without_their_factor_are_not_correct(tmp_path):
    line, log = run_cell(tmp_path, WRONG)
    assert line["correct"] is False
    assert "'expert_layers': False" in log


def test_held_swaps_are_the_near_ties_that_move_a_held_expert():
    import numpy as np
    from archs import exaone_moe as arch
    #            held: 0, 1        elsewhere: 2 ..
    v = np.array([0.10, 0.65, 0.90, 0.80, 0.70, 0.60])
    # top 3 = {2, 3, 4}; held 1 would enter for the weakest chosen (4)
    assert arch.held_swaps(v, 2, 3, 0.2) == [(pytest.approx(0.05), 4, 1)]
    assert arch.held_swaps(v, 2, 3, 0.04) == []
    # top 3 = {0, 1, 2}: both held experts may leave for the best unchosen
    v = np.array([0.90, 0.80, 0.70, 0.60, 0.50])
    assert [(o, i) for _, o, i in arch.held_swaps(v, 2, 3, 0.35)] == [
        (1, 3), (0, 3)]
    # a tie between two experts held elsewhere is no swap of this device's
    assert arch.held_swaps(np.array([0.1, 0.2, 0.9, 0.8, 0.7, 0.69]),
                           2, 3, 0.05) == []


def test_a_token_of_the_other_side_of_a_tie_is_within_the_margin():
    """``tie_aware_shortfall`` at the toy width: the token a held
    expert's swap makes best is under the plain reference's best, and
    within the limit under the routing the search finds; with no tie
    allowed (delta 0), or for a token no swap explains, the plain
    shortfall stands."""
    import jax.numpy as jnp
    import numpy as np
    from archs import exaone_moe as arch
    with open(os.path.join(TOY, "configs", "toy-exaone.json")) as f:
        conf = json.load(f)
    cfg = arch.transformer_config(conf, max_len=64, remat=False,
                                  attention_impl="dense")
    params = arch.init_params(cfg, 7, "float32")
    ids = jnp.asarray(np.random.default_rng(3).integers(
        1, conf["vocab_size"], (1, 40)), jnp.int32)
    ref = arch.reference(conf, params, ids)
    width, held, k = (arch._router_width(conf), conf["num_experts"],
                      conf["num_experts_per_tok"])
    found = None
    for at in range(8, 40):
        for i, (y, _) in ref["experts"].items():
            moe = params[f"layer_{i}"]["moe"]
            v = np.asarray(arch._selection_scores(
                moe["gate"], moe["gate_bias"], y[0, at][None]))[0]
            for gap, out, into in arch.held_swaps(v, held, k, 1.0):
                row = np.zeros((width,), np.float32)
                row[out], row[into] = -1.0, 1.0
                other = arch.reference(conf, params, ids, nudge={
                    i: jnp.zeros((1, 40, width)).at[0, at].set(row)})
                token = int(other["logits"][0, at].argmax())
                if token != int(ref["logits"][0, at].argmax()):
                    found = (at, token, gap)
                    break
            if found:
                break
        if found:
            break
    assert found, "no swap at this width moves an argmax"
    at, token, gap = found
    plain = arch.tie_aware_shortfall(conf, params, ids, ref, at, token,
                                     limit=1e-6, delta=0.0)
    assert plain["plain"] == plain["shortfall"] > 1e-6
    assert plain["passes"] == 0 and plain["swaps"] == []
    aware = arch.tie_aware_shortfall(conf, params, ids, ref, at, token,
                                     limit=1e-6, delta=gap * 1.01 + 1e-9)
    assert aware["plain"] == plain["plain"] and aware["shortfall"] <= 1e-6
    assert 1 <= len(aware["swaps"]) <= 2 and aware["passes"] >= 1
    # the reference's own best needs no search
    best = int(ref["logits"][0, at].argmax())
    assert arch.tie_aware_shortfall(conf, params, ids, ref, at, best,
                                    limit=1e-6, delta=1.0)["passes"] == 0
    # a token far from every honest routing's best stays far
    worst = int(ref["logits"][0, at].argmin())
    far = arch.tie_aware_shortfall(conf, params, ids, ref, at, worst,
                                   limit=0.35, delta=1.0, passes=6)
    assert far["shortfall"] > 0.35 and far["passes"] == 6


def conf_of():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "k-exaone-236b-a23b-serve-ep8.json")) as f:
        return json.load(f)


def test_the_real_spec_and_toy_spec_name_the_same_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TOY, "BENCHMARK-hybrid.json")) as f:
        toy = json.load(f)
    cells = {w["name"]: w for w in real["workloads"]}
    assert [w["name"] for w in toy["workloads"]] == [CELL]
    assert cells[CELL]["traffic"] == TRAFFIC
    assert len(real["workloads"]) == len(cells)     # a name is one cell
    for w in toy["workloads"]:
        assert cells[w["name"]]["traffic"] == w["traffic"]
        assert cells[w["name"]]["chips"] == w["chips"] == 1
        # cell by cell, the toy spec lists what the real one lists
        for kind in ("end_to_end", "per_layer"):
            assert {m["name"] for m in real[kind]
                    if w["name"] in m.get("workloads", [w["name"]])} == {
                m["name"] for m in toy[kind]
                if w["name"] in m.get("workloads", [w["name"]])}, (
                w["name"], kind)
    for m in toy["per_layer"]:
        importlib.import_module(f"layer_metrics.{m['name']}")
    # the first cut's traffic went with PR 40: no cell and no file
    assert not any("mixed-length-open" == w["traffic"]
                   for w in real["workloads"])
    assert not os.path.exists(os.path.join(
        ROOT, "benchmarks", "traffic", "mixed-length-open.json"))


def test_arch_maps_every_key_and_refuses_the_rest():
    from archs import exaone_moe as arch
    conf = conf_of()
    cfg = arch.transformer_config(conf, max_len=16384)
    assert (cfg.embed_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (
        6144, 64, 8, 128)
    assert (cfg.mlp_dim, cfg.expert_dim, cfg.moe_shared_dim) == (
        18432, 2048, 2048)
    assert (cfg.moe_experts, cfg.moe_held, cfg.moe_top_k) == (128, 16, 8)
    assert cfg.layer_attn == ("window",) * 3 + ("global",) + (
        "window",) * 3 + ("global",)
    assert cfg.layer_mlp == ("dense",) + ("sparse",) * 7
    assert cfg.attn_window == 128 and not cfg.rope_global
    assert cfg.moe_router == "sigmoid" and cfg.moe_routed_scale == 2.5
    assert cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-5
    with pytest.raises(ValueError, match="attention_bias"):
        arch.transformer_config(dict(conf, attention_bias=True),
                                max_len=16384)
    with pytest.raises(ValueError, match="scoring_func"):
        arch.transformer_config(dict(conf, scoring_func="softmax"),
                                max_len=16384)
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        arch.transformer_config(dict(conf, num_nextn_predict_layers=1),
                                max_len=16384)


def test_the_file_keeps_every_published_number():
    """The catalog's copy of config.json, where this sandbox has it:
    every number under the same key, but for the four in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "K-EXAONE-236B-A23B")
    conf = conf_of()
    assert conf["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if conf.get(k) != v}
    assert differ == set(conf["reduced"]) == set(conf["reduced_from"])
    assert all(conf["reduced_from"][k] == entry["config"][k] for k in differ)


def test_arch_counts_are_the_configuration_files():
    from archs import exaone_moe as arch

    from edl_tpu.models.transformer import param_count
    conf = conf_of()
    assert arch.param_count(conf) == conf["memory"]["parameters"]
    assert arch.param_count(conf) == param_count(
        arch.transformer_config(conf, max_len=16384))
    assert arch.expert_params(conf) * 2 == 75_497_472         # 75.5 MB
    assert (arch.sparse_layers(conf), arch.window_layers(conf)) == (7, 6)
    assert arch.kv_bytes_per_token(conf) == 8192        # two global layers
    # the issue's expectation: 3.3 GB shared, 6 live slots touch
    # 16 x (1 - 0.9375^6) = 5.1 held experts a layer: 2.7 GB of experts
    need = arch.decode_step_min_bytes(conf, 5.14, 0.0)
    assert 5.9e9 < need < 6.1e9
    assert 3.2e9 < arch.decode_step_min_bytes(conf, 0.0, 0.0) < 3.4e9


# a 45 s window of the cell: 300 ticks x 4 token steps, 6 slots live
COUNTERS = {
    "window_s": 45.0, "steps_per_sync": 4,
    "moe_assignments": 50_000, "moe_assignments_routed": 400_000,
    "decode_kv_tokens_window_read": 1_843_200,
    "decode_kv_tokens_window_need": 900_000,
    "trace_span_counters": {
        "moe_assignments": 5_000, "decode_kv_tokens_window_need": 80_000},
}
TRACE = {"window_s": 4.0,
         "ops": {"window_attend.3_bf16_12_8_16_128_": 0.010,
                 "window_append.3_bf16_12_8_128_256_": 0.006,
                 "decode_attend.1_bf16_12_8_16_128_": 0.5},
         "modules": {"jit__step_impl": {"count": 40, "total_s": 3.2}}}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["kv_window_read_ratio", "moe_held_assignment_share",
       "window_attention_roofline"]


def ctx(counters, trace):
    return {"counters": counters, "trace": trace, "peak": PEAK,
            "conf": conf_of()}


def reader(name):
    return importlib.import_module(f"layer_metrics.{name}").read


def test_new_readers_on_a_recorded_counter_set():
    from archs import exaone_moe as arch
    c = ctx(dict(COUNTERS), TRACE)
    assert reader("kv_window_read_ratio")(c) == pytest.approx(2.048)
    assert reader("moe_held_assignment_share")(c) == pytest.approx(12.5)
    # 80,000 window positions a layer in the span, six window layers,
    # against the 16 ms of the two window kernels (the global layers'
    # decode_attend is not theirs)
    flops, nbytes = arch.window_attention_min(conf_of(), 80_000)
    assert nbytes == 6 * 80_000 * 4096 and flops == 6 * 80_000 * 32768
    assert reader("window_attention_roofline")(c) == pytest.approx(
        100.0 * (nbytes / 819e9) / 0.016)
    assert 0 < reader("window_attention_roofline")(c) <= 100.0


@pytest.mark.parametrize("name", NEW)
def test_new_readers_say_nothing_where_there_is_nothing(name):
    """The parent's engine has none of these counters, and an untraced
    run has no trace: the reader returns None and never raises."""
    old = {"window_s": 45.0, "steps_per_sync": 4, "moe_prefill_drops": 0,
           "moe_assignments": 7}
    assert reader(name)(ctx(old, TRACE)) is None
    assert reader(name)(ctx(dict.fromkeys(COUNTERS, 0), TRACE)) is None
    if "roofline" in name:
        assert reader(name)(ctx(dict(COUNTERS), None)) is None
        untapped = {k: v for k, v in COUNTERS.items()
                    if k != "trace_span_counters"}
        assert reader(name)(ctx(untapped, TRACE)) is None
        no_kernels = dict(TRACE, ops={"decode_attend.1": 0.5})
        assert reader(name)(ctx(dict(COUNTERS), no_kernels)) is None


def test_the_prompts_are_the_mixture_and_fit_the_generator():
    from generators import open_trace
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           f"{TRAFFIC}.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    plan = open_trace.schedule(traffic, 2147483659, float(seconds), 19200)
    reqs = [r for r in plan["requests"] if r["window"]]
    n = round(traffic["rate_per_s"] * seconds)
    assert len(reqs) == n == 56 and traffic["rate_per_s"] * seconds == \
        pytest.approx(n)
    parts = traffic["prompt_tokens"]["parts"]
    assert traffic["prompt_tokens"]["dist"] == "mixture"
    assert [p["share"] for p in parts] == [0.875, 0.125]
    lens = sorted(len(r["prompt"]) for r in reqs)
    short = [x for x in lens if x <= 2048]
    long = [x for x in lens if x >= 4096]
    assert len(short) + len(long) == n
    assert abs(len(long) / n - 0.125) <= 1.0 / n
    assert 32 <= short[0] and long[-1] <= 15360
    assert all(len(r["prompt"]) + r["max_new"] <= 16384 for r in reqs)
    shapes = open_trace.shapes(traffic, float(seconds), 16)
    assert shapes["max_total"] <= 16384
    # a sweep's rate is this file at another rate: twice the requests
    # are the same mixture's quantiles, still 7 : 1
    twice = dict(traffic, rate_per_s=2 * traffic["rate_per_s"])
    lens2 = open_trace.cycle(twice, float(seconds))[0]
    assert len(lens2) == 2 * n and (lens2 >= 4096).sum() == 2 * len(long)


def test_the_recut_traffic_states_its_rate_and_its_warm_traffic():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           f"{TRAFFIC}.json")) as f:
        traffic = json.load(f)
    assert traffic["generator"] == "open_trace" and traffic["loop"] == "open"
    # what the first cut (mixed-length-open.json, gone with PR 40) had
    # and the re-cut keeps; a new trace_seed
    assert traffic["trace_seed"] == 20261001 != 20260928
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32,
        "max": 1024}
    assert traffic["gaps"] == {"dist": "exponential"}
    assert (traffic["shared_prefix"], traffic["client_threads"],
            traffic["probe_tokens"], traffic["drain_seconds"]) == (
        False, 96, 656, 30.0)
    # a 1,024-token answer lives 11.5 s: the warm traffic outlasts it
    assert traffic["warm_seconds"] == 20.0 > 1024 * 0.01123
    assert "of the knee" in traffic["rate_note"]
    with open(os.path.join(TOY, "traffic", f"{TRAFFIC}.json")) as f:
        toy = json.load(f)
    assert toy["prompt_tokens"]["dist"] == "mixture"
    assert toy["trace_seed"] == traffic["trace_seed"]
