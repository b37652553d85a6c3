"""The benchmark never measures a CPU under a device metric's name.

``benchmarks/run.py`` is the repo's one benchmark; every key it prints
is a device metric.  Without the TPU chips a cell asks for it must
refuse: exit code 2, nothing on stdout that could pass for a result.
This test reads the benchmark, it does not edit it.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CELLS = {w["name"]: w["chips"] for w in json.load(_f)["workloads"]}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_benchmark_refuses_the_cpu(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 2, proc.stderr
    assert proc.stdout == ""
    assert f"needs {CELLS[cell]} TPU chip(s)" in proc.stderr
