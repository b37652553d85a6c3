"""The decoder-hybrid-decoder runner (``runners/serve_sambay.py``) and
what PR 45 added beside it: ``run.py`` end to end on the CPU at toy
widths for the new cell (files under ``tests/toy``, spec
``BENCHMARK-sambay.json``) as it is, with plain attention in place of
differential attention, and with the cross layer reading its rows one
short.  What needs no subprocess (the configuration file against the
catalog, the arch module's counts, the four new readers on canned
counters, the traffic file against the generator, prefill with the cut,
chunks, decode past two windows, pool hits, migration) is tier-1:
``tests/test_phi4flash.py``."""
import importlib
import json
import os
import subprocess
import sys

from test_run_cpu import DRIVER as _DRIVER

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "toy")
CELL = "serve-sambay-reason-open"
CONFIG = "phi-4-mini-flash-reasoning-serve"

DRIVER = _DRIVER.replace('"/BENCHMARK.json"', '"/BENCHMARK-sambay.json"')
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
    assert ("'cut_counted': True, 'mixer_layers': True, "
            "'window_layers': True, 'attention_layers': True, "
            "'memory_units': True, 'carried_state': True, "
            "'block_logits': True, 'cache_logits': True, "
            "'cross_steps': True") in log
    assert "pooled-equal True" in log and "prefix-hit 1" in log
    assert "reference argmax agrees on 17/17" in log


def wrong(patch: str) -> str:
    out = DRIVER.replace(
        "import run\n", "import run\n"
        "from edl_tpu.models import transformer as _t\n" + patch, 1)
    assert out != DRIVER
    return out


# plain attention in place of differential attention, in the PROGRAM
LAM0 = wrong(
    "_lam = _t.Block._lambda\n"
    "_t.Block._lambda = lambda self, w: (0.0, _lam(self, w)[1])\n")
# the cross layer reads the full layer's rows one short
SHORT = wrong(
    "_cross = _t.Block._cross_attention\n"
    "_t.Block._cross_attention = lambda self, q, pos, mask, lent: _cross(\n"
    "    self, q, pos - 1, mask, lent)\n")


def test_plain_attention_is_not_correct(tmp_path):
    line, log = run_cell(tmp_path, LAM0)
    assert line["correct"] is False
    assert "'attention_layers': False" in log


def test_rows_one_short_are_not_correct(tmp_path):
    line, log = run_cell(tmp_path, SHORT)
    assert line["correct"] is False
    assert "'cross_steps': False" in log


def test_the_real_spec_and_toy_spec_name_the_same_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TOY, "BENCHMARK-sambay.json")) as f:
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
    assert sum(w["chips"] == 4 for w in real["workloads"]) == 1
