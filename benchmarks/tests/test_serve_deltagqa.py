"""The delta-rule + gated-GQA runner (``runners/serve_deltagqa.py``) and
what PR 52 added beside it: ``run.py`` end to end on the CPU at toy
widths for the new cell (files under ``tests/toy``, spec
``BENCHMARK-deltagqa.json``) as it is and with beta left undoubled in the
program, ``archs/solar_open2.py``'s refusal of keys it does not map, the
new readers on a recorded counter set, and the traffic file against the
generator."""
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
CELL = "serve-kda-agent-closed"
CONFIG = "solar-open2-250b-serve-ep8"
NEW = {"prefix_attend_roofline", "kv_chunk_read_ratio",
       "long_attend_roofline", "session_reattach_ms",
       "kv_state_reprefill_share", "deltagqa_step_mfu"}

DRIVER = _DRIVER.replace('"/BENCHMARK.json"', '"/BENCHMARK-deltagqa.json"')
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
    assert "expert sets differ in 0.000%" in log
    checks = log.split("deltagqa checks ")[1].split("}")[0]
    assert "False" not in checks, checks
    for name in ("served_margin", "pooled_margin", "session_resumed",
                 "session_margin", "long_chunk", "long_step",
                 "carried_state", "cache_logits", "held_pairs_recount"):
        assert f"'{name}': True" in checks
    assert "pooled-equal True" in log and "prefix-hit 1" in log
    assert "session probe: turn 2" in log


# the same cell with beta left undoubled in the PROGRAM (the published
# switch ignored: another model under this name)
WRONG = DRIVER.replace(
    "import run\n", "import run\n"
    "import archs.solar_open2 as arch\n"
    "_cfg = arch.transformer_config\n"
    "arch.transformer_config = lambda conf, **kw: _cfg(\n"
    "    conf, **dict(kw, kda_neg_eigval=False))\n", 1)
assert WRONG != DRIVER


def test_an_undoubled_beta_is_not_correct(tmp_path):
    line, log = run_cell(tmp_path, WRONG)
    assert line["correct"] is False
    assert "'mixer_layers': False" in log and "'carried_state': False" in log
    assert "'attention_layers': True" in log


def conf_of():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{CONFIG}.json")) as f:
        return json.load(f)


def test_the_real_spec_and_toy_spec_name_the_same_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TOY, "BENCHMARK-deltagqa.json")) as f:
        toy = json.load(f)
    cells = {w["name"]: w for w in real["workloads"]}
    assert cells[CELL]["config"] == CONFIG and cells[CELL]["chips"] == 1
    for w in toy["workloads"]:
        assert cells[w["name"]]["traffic"] == w["traffic"]
    listed = {m["name"] for m in real["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {m["name"] for m in toy["per_layer"]} and NEW <= listed
    for m in toy["per_layer"]:
        importlib.import_module(f"layer_metrics.{m['name']}")
    for m in real["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
        if CELL in m.get("workloads", []):     # a closed loop has no p50
            assert m["moves"] in ("serve_tokens_per_s", "setup_s")
    assert real["workloads"][-1]["name"] == CELL
    assert len(real["workloads"]) == 12


def test_unmapped_key_is_refused():
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    arch = importlib.import_module("archs.solar_open2")
    conf = conf_of()
    arch._check(conf)
    with pytest.raises(ValueError, match="maps no key"):
        arch._check({**conf, "qk_norm": True})
    with pytest.raises(ValueError, match="kda_use_full_proj"):
        arch._check({**conf, "kda_use_full_proj": True})
    assert arch.layer_kinds(conf) == ["global", "kda", "kda", "kda"]
    assert arch.param_count(conf) == conf["memory"]["parameters"]
    assert set(conf["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                    "vocab_size"}


def test_traffic_fits_the_configuration():
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    gen = importlib.import_module("generators.closed_sessions")
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "agent-turns-closed.json")) as f:
        traffic = json.load(f)
    run = conf_of()["run"]
    shapes = gen.shapes(traffic, 45.0, run["kv_block"])
    assert 32768 <= min(shapes["doc_lens"]) < max(shapes["doc_lens"]) <= 98304
    assert shapes["max_total"] <= run["max_len"]
    assert traffic["clients"] == 6 and traffic["questions_per_session"] == 40
    # six sessions' chains at the mean length fit the pool
    mean = sum(shapes["doc_lens"]) / len(shapes["doc_lens"]) + 20 * 288
    assert 6 * mean / run["kv_block"] < run["kv_pool_blocks"]


SPAN = {"kv_prefill_pairs": 3.0e7, "kv_prefill_rows_live": 7.0e5,
        "kv_prefill_tokens": 9000, "kv_prefill_tokens_skipped": 8000,
        "decode_kv_tokens_live": 4.0e7, "ssm_state_steps": 1800,
        "moe_assignments": 100, "moe_assignments_routed": 800,
        "first_tokens": 4}


def test_new_readers_read_a_recorded_counter_set():
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    import costs
    conf = conf_of()
    ctx = {"conf": conf, "peak": costs.peaks("TPU v5 lite"),
           "counters": {"trace_span_counters": SPAN,
                        "kv_prefill_rows_live": 100, "kv_prefill_rows_read":
                        104, "kv_prefill_tokens": 1000,
                        "kv_prefill_tokens_skipped": 600,
                        "kv_state_reprefill_tokens": 150},
           "trace": {"window_s": 4.0, "busy_s": 3.5,
                     "ops": {"decode_attend.3_bf16_8_64_128_": 0.30},
                     "modules": {"jit_load": {"count": 2, "total_s": 0.006,
                                              "durations_s": [0.002, 0.004]}},
                     "scopes": {"attn/prefix_chunk": 0.05}}}
    got = {n: importlib.import_module(f"layer_metrics.{n}").read(ctx)
           for n in NEW}
    assert got["kv_chunk_read_ratio"] == pytest.approx(1.04)
    assert got["kv_state_reprefill_share"] == pytest.approx(37.5)
    assert got["session_reattach_ms"] == pytest.approx(3.0)
    for name in ("prefix_attend_roofline", "long_attend_roofline",
                 "deltagqa_step_mfu"):
        assert 0 < got[name] < 100, (name, got[name])
    # a program without the counters (the parent commit): nothing, no raise
    bare = {**ctx, "counters": {}, "trace": {"window_s": 4.0, "ops": {},
                                             "modules": {}}}
    assert all(importlib.import_module(f"layer_metrics.{n}").read(bare)
               is None for n in NEW)
