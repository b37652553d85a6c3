"""``run.py`` end to end at toy widths on the CPU, one run of every
cell, with the files under ``tests/toy``.  The switch past the no-chip
refusal is the TEST's: it replaces ``run.check_devices``; ``run.py``
has no argument for it."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

DRIVER = """
import sys
sys.path.insert(0, {bench!r}); sys.path.insert(0, {root!r})
import run
toy = {toy!r}
run.SPEC_PATH = toy + "/BENCHMARK.json"
run.CONFIG_DIR = toy + "/configs"
run.TRAFFIC_DIR = toy + "/traffic"
run.check_devices = lambda chips: None
rc = run.main(sys.argv[1:]); sys.stdout.flush(); import os; os._exit(rc)
"""


def run_cell(cell, seconds, devices=1, tmp_path=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    code = DRIVER.format(bench=os.path.join(ROOT, "benchmarks"), root=ROOT,
                         toy=os.path.join(HERE, "toy"))
    out = subprocess.run(
        [sys.executable, "-c", code, "--workload", cell, "--seed",
         "2147483659", "--seconds", str(seconds), "--trace", "0"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,devices,metrics", [
    ("train-steady", 1, {"train_tokens_per_s_per_chip", "setup_s"}),
    ("train-mesh4", 4, {"train_tokens_per_s_per_chip", "setup_s"}),
    ("serve-chat-open", 1, {"serve_tokens_per_s", "serve_latency_p50_s",
                            "serve_latency_p90_s", "setup_s"}),
    ("serve-doc-sessions", 1, {"serve_tokens_per_s", "setup_s"}),
])
def test_cell_runs_on_cpu_at_toy_width(cell, devices, metrics, tmp_path):
    line = run_cell(cell, 4, devices, tmp_path)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"   # never a device metric


def test_run_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "train-steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env=env, cwd=ROOT)
    assert out.returncode == 2
    assert not out.stdout.strip().startswith("{")
    assert "{" not in (out.stdout.strip().splitlines() or [""])[-1]
