"""The all-latent runner (``runners/serve_mla.py``) and what PR 41 added
beside it: ``run.py`` end to end on the CPU at toy widths for the new
cell (files under ``tests/toy``, spec ``BENCHMARK-mla.json``) as it is
and with the rotation left out of the program, the two new readers on a
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
CELL = "serve-mla-docs-closed"
CONFIG = "openpangu-ultra-moe-718b-serve-ep16"

DRIVER = _DRIVER.replace('"/BENCHMARK.json"', '"/BENCHMARK-mla.json"')
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
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"   # never a device metric
    # float32 at toy width: the program routes as the reference does
    assert "expert sets differ in 0.000%" in log
    assert ("'served_margin': True, 'pooled_margin': True, "
            "'nothing_dropped': True, 'every_token_routed': True, "
            "'held_pairs_recount': True, 'attention_layers': True, "
            "'absorbed_attention': True, 'expert_layers': True, "
            "'routed_experts': True, 'block_logits': True, "
            "'cache_logits': True") in log
    assert "pooled-equal True" in log and "prefix-hit 1" in log
    held = log.split("held pairs on the cold probe: the engine computed ")[1]
    computed, recount = held.split(", the host recounts ")
    assert int(computed) == int(recount.split(" ")[0]) > 0


# the same cell with q_pe and k_pe not rotated in the PROGRAM
WRONG = DRIVER.replace(
    "import run\n", "import run\n"
    "from archs import pangu_ultra_moe as arch\n"
    "_cfg = arch.transformer_config\n"
    "arch.transformer_config = lambda conf, **kw: _cfg(\n"
    "    conf, **dict(kw, mla_rope=False))\n", 1)
assert WRONG != DRIVER


def test_a_program_without_the_rotation_is_not_correct(tmp_path):
    line, log = run_cell(tmp_path, WRONG)
    assert line["correct"] is False
    assert "'attention_layers': False" in log
    assert "'absorbed_attention': False" in log
    assert "'expert_layers': True" in log


def conf_of():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{CONFIG}.json")) as f:
        return json.load(f)


NEW = ["mla_attend_roofline", "latent_chunk_prefill_mfu"]


def test_the_real_spec_and_toy_spec_name_the_same_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TOY, "BENCHMARK-mla.json")) as f:
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
    ends = {m["name"] for m in real["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert ends == {"serve_tokens_per_s", "setup_s"}
    for m in real["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
        if CELL in m.get("workloads", []):
            # a listed cell reports the end-to-end metric it moves
            assert m["moves"] in ends, m["name"]
    assert sum(w["chips"] == 4 for w in real["workloads"]) == 1
    assert len(real["workloads"]) == 9 and len(real["configs"]) == 8


# a 45 s window of the cell; the engine's counters between the trace's
# edges: 60 step programs x 4 token steps x 5 live slots x 5 layers over
# 16k rows; 100 chunks of 512 tokens at a mean offset of 8k
SPAN = {"latent_tokens_live": 60 * 4 * 5 * 5 * 16_384,
        "latent_decode_calls": 60 * 4 * 5 * 5,
        "latent_prefill_tokens": 51_200,
        "latent_prefill_pairs": 5 * 51_200 * 8_448,
        "latent_prefill_rows_live": 5 * 100 * 8_704,
        "moe_assignments": 102_400, "moe_assignments_routed": 1_638_400}
COUNTERS = {"window_s": 45.0, "steps_per_sync": 4,
            "trace_span_counters": SPAN}
TRACE = {"window_s": 4.0,
         "ops": {"latent_attend.3_bf16_12_128_640_": 0.9,
                 "latent_append.2_bf16_12_32768_640_": 0.01,
                 "fusion.12_bf16_96_2048_": 0.3},
         "modules": {"jit__step_impl": {"count": 60, "total_s": 0.3},
                     "jit_mid": {"count": 95, "total_s": 3.4},
                     "jit_fin": {"count": 5, "total_s": 0.2},
                     "jit__insert_impl": {"count": 5, "total_s": 0.01}}}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def ctx(counters, trace):
    return {"counters": counters, "trace": trace, "peak": PEAK,
            "conf": conf_of()}


def reader(name):
    return importlib.import_module(f"layer_metrics.{name}").read


def test_new_readers_on_a_recorded_counter_set():
    from archs import pangu_ultra_moe as arch
    conf = conf_of()
    c = ctx(dict(COUNTERS), TRACE)
    flops, nbytes = arch.latent_attention_min(
        conf, SPAN["latent_tokens_live"], SPAN["latent_decode_calls"])
    # at the ridge: the two terms lie within a few percent
    assert 0.95 < (flops / 197e12) / (nbytes / 819e9) < 1.05
    assert reader("mla_attend_roofline")(c) == pytest.approx(
        100.0 * max(flops / 197e12, nbytes / 819e9) / 0.91)
    assert 0 < reader("mla_attend_roofline")(c) <= 100.0
    want = arch.chunk_prefill_flops(
        conf, 51_200, SPAN["latent_prefill_pairs"],
        SPAN["latent_prefill_rows_live"], 1 / 16)
    assert reader("latent_chunk_prefill_mfu")(c) == pytest.approx(
        100.0 * want / 197e12 / 3.6)
    assert 0 < reader("latent_chunk_prefill_mfu")(c) <= 100.0
    # a token's matmuls, its pairs and its share of the expansion
    assert arch.active_matmul_params(conf) == pytest.approx(
        5 * 196_575_232 + 424_673_280 + 4 * (1_966_080 + 47_185_920)
        + 4 * 8 / 16 * 47_185_920)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_say_nothing_where_there_is_nothing(name):
    """The parent's engine has none of these counters, and an untraced
    run has no trace: the reader returns None and never raises; nor
    under another cell's configuration."""
    old = {"window_s": 45.0, "steps_per_sync": 4, "moe_prefill_drops": 0,
           "trace_span_counters": {"latent_tokens_live": 5,
                                   "moe_assignments": 7}}
    assert reader(name)(ctx(old, TRACE)) is None
    assert reader(name)(ctx({"window_s": 45.0}, TRACE)) is None
    assert reader(name)(ctx(dict(COUNTERS), None)) is None
    empty = dict(TRACE, ops={"decode_attend.1": 0.5},
                 modules={"jit__step_impl": {"count": 1, "total_s": 1.0}})
    assert reader(name)(ctx(dict(COUNTERS), empty)) is None
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4.0-h-small-serve-ep2.json")) as f:
        other = dict(ctx(dict(COUNTERS), TRACE), conf=json.load(f))
    assert reader(name)(other) is None


def test_the_traffic_fits_the_engine():
    from generators import closed_sessions
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "doc-reask-closed.json")) as f:
        traffic = json.load(f)
    conf = conf_of()
    assert traffic["generator"] == "closed_sessions"
    assert (traffic["loop"], traffic["clients"], traffic["think_seconds"],
            traffic["sessions_in_trace"]) == ("closed", 6, 0.0, 24)
    assert traffic["questions_per_session"] == 4
    assert traffic["output_tokens"] == 64 and traffic["session_affinity"]
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "doc-qa-closed.json")) as f:
        old = json.load(f)
    assert (traffic["warm_turns"], traffic["drain_seconds"]) == (
        old["warm_turns"], old["drain_seconds"])
    shapes = closed_sessions.shapes(traffic, 45.0, conf["run"]["kv_block"])
    docs = shapes["doc_lens"]
    assert traffic["document_tokens"]["min"] <= min(docs)
    assert max(docs) <= traffic["document_tokens"]["max"]
    # the cut that stands (ISSUE 41's one rule; the file's ``cut_note``)
    assert traffic["document_tokens"] == {
        "dist": "uniform_quantiles", "min": 4096, "max": 16384}
    assert "8,192-24,576" in traffic["cut_note"]
    assert sorted(docs)[len(docs) // 2 - 1] < 10_240 < sorted(docs)[
        len(docs) // 2]
    assert shapes["max_total"] < conf["run"]["max_len"] == 32_768
    # a session's chain: 64-263 blocks of 64 rows; the live sessions'
    # chains fit the pool whatever the documents are
    assert min(docs) // 64 == 68 and shapes["max_total"] // 64 == 262
    from runners import serve_mla
    depths = serve_mla.chain_depths(conf, traffic, 45.0)
    assert min(depths) == 1500 // 64 and max(depths) == 263
    assert {1 << (n - 1).bit_length() for n in depths} == {32, 128, 256, 512}
    run = conf["run"]
    assert run["kv_max_sessions"] * (shapes["max_total"] // 64 + 1) < run[
        "kv_pool_blocks"]
