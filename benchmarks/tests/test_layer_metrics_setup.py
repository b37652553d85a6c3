"""The readers of the program-build ledger (PR 50): each on a made
ledger, None on a program without one (the parent commit), the entries
of ``BENCHMARK.json`` that name them, and two toy ``serve-chat-open``
runs on one compile cache: the first compiles, the second reads back."""
import importlib
import json
import os
import subprocess
import sys

import pytest

from edl_tpu.obs import ledger as obs_ledger

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"

SETUP_READERS = ("setup_trace_lower_s", "setup_compile_s",
                 "setup_cache_hit_share", "setup_warm_run_s",
                 "setup_programs_built", "setup_state_s",
                 "setup_unlabelled_build_s")
READERS = (*SETUP_READERS, "serve_build_s_in_window")


def reader(name):
    return importlib.import_module(f"layer_metrics.{name}").read


def _program(led, trace, lower, compile_, hit=None):
    """One program's events, as jax.monitoring delivers them.  (Lengths
    that reach back past the event before them would be taken to
    contain it: the ledger's clock is real, so these are booked and
    then set to the lengths the case wants.)"""
    led.on_duration(TRACE, 0.0, fun_name="f")
    led.on_duration(LOWER, 0.0, fun_name="jit(f)")
    if hit is not None:
        led.on_event(REQUEST)
        if hit:
            led.on_event(HIT)
    led.on_duration(COMPILE, 0.0, fun_name="jit(f)")
    return {"trace_s": trace, "lower_s": lower, "compile_s": compile_}


@pytest.fixture
def made(monkeypatch):
    """A ledger with two engine families, a pool-commit program, a
    state set-up and two unlabelled compiles, in place of the
    process's."""
    led = obs_ledger.ProgramBuildLedger()
    want = {}
    with led.build("engine", "prefill", key=(32, 1)):
        want["build/engine/prefill"] = _program(led, 2.0, 3.0, 4.0, hit=True)
    with led.build("engine", "step"):
        want["build/engine/step"] = _program(led, 1.0, 0.5, 8.0, hit=False)
    with led.build("kv", "pool_commit", key=3):
        want["build/kv/pool_commit"] = _program(led, 0.25, 0.25, 0.5,
                                                hit=True)
    with led.setup("engine"):
        want["setup/engine/state"] = _program(led, 0.5, 0.5, 1.0, hit=True)
    want["build/other/f"] = _program(led, 0.125, 0.125, 0.25)
    _program(led, 0, 0, 0)
    with led._lock:
        for row, fields in want.items():
            led._rows[tuple(row.split("/"))].update(fields)
        for row, run in (("build/engine/prefill", 0.75),
                         ("build/engine/step", 0.0),
                         ("build/kv/pool_commit", 0.125),
                         ("setup/engine/state", 6.0)):
            led._rows[tuple(row.split("/"))]["run_s"] = run
    monkeypatch.setattr(obs_ledger, "PROGRAM_BUILDS", led)
    return led


WANT = {
    "setup_trace_lower_s": 2.0 + 3.0 + 1.0 + 0.5 + 0.25 + 0.25,
    "setup_compile_s": 4.0 + 8.0 + 0.5,
    "setup_cache_hit_share": 75.0,          # 3 of the 4 that asked
    "setup_warm_run_s": 0.75 + 0.125,
    "setup_programs_built": 6.0,
    "setup_state_s": 0.5 + 0.5 + 1.0 + 6.0,
    "setup_unlabelled_build_s": 0.5,
}


@pytest.mark.parametrize("name", SETUP_READERS)
def test_reader_on_a_made_ledger(made, name):
    assert reader(name)({"counters": {}, "trace": None}) == \
        pytest.approx(WANT[name])


def test_the_seconds_readers_tile_the_ledger(made):
    """Every second the ledger booked is in exactly one of the five."""
    ctx = {"counters": {}, "trace": None}
    booked = sum(v for k, v in made.totals().items() if k.endswith("_s"))
    assert sum(reader(n)(ctx) for n in (
        "setup_trace_lower_s", "setup_compile_s", "setup_warm_run_s",
        "setup_state_s", "setup_unlabelled_build_s")) == \
        pytest.approx(booked)


@pytest.mark.parametrize("name", SETUP_READERS)
def test_reader_says_nothing_on_a_program_without_the_ledger(
        monkeypatch, name):
    """The parent commit's ``obs/ledger.py`` has no ``PROGRAM_BUILDS``:
    None, never a raise, and the line leaves the metric out.  The same
    for a ledger that has booked nothing."""
    monkeypatch.delattr(obs_ledger, "PROGRAM_BUILDS")
    assert reader(name)({"counters": {}, "trace": None}) is None
    monkeypatch.setattr(obs_ledger, "PROGRAM_BUILDS",
                        obs_ledger.ProgramBuildLedger(), raising=False)
    assert reader(name)({"counters": {}, "trace": None}) is None


def test_hit_share_is_none_when_no_request_used_the_cache(monkeypatch):
    led = obs_ledger.ProgramBuildLedger()
    with led.build("engine", "step"):
        _program(led, 0, 0, 0)
    monkeypatch.setattr(obs_ledger, "PROGRAM_BUILDS", led)
    ctx = {"counters": {}, "trace": None}
    assert reader("setup_cache_hit_share")(ctx) is None
    assert reader("setup_programs_built")(ctx) == 1.0


def test_build_seconds_in_the_window_come_from_stats():
    read = reader("serve_build_s_in_window")
    assert read({"counters": {"program_build_s": 0.0}}) == 0.0
    assert read({"counters": {"program_build_s": 1.5,
                              "program_builds": 3}}) == 1.5
    # the parent's engine has no such key
    assert read({"counters": {"prefill_stall_s": 0.2}}) is None


def test_every_new_entry_has_a_file_a_docstring_moves_and_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = [w["name"] for w in spec["workloads"]]
    serve = [c for c in cells if c.startswith("serve-")]
    by_name = {m["name"]: m for m in spec["per_layer"]}
    # appended, in the table's order, after everything that was there
    assert [m["name"] for m in spec["per_layer"]][-8:] == list(READERS)
    for name in READERS:
        m = by_name[name]
        mod = importlib.import_module(f"layer_metrics.{name}")
        assert (mod.__doc__ or "").strip(), name
        assert callable(mod.read)
        assert m["source"] == "program_span"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if name == "serve_build_s_in_window":
            assert m["moves"] == "serve_tokens_per_s"
            assert m["workloads"] == serve
        else:
            assert m["moves"] == "setup_s" and m["workloads"] == cells
        assert m["layer"] == ("state placement" if name == "setup_state_s"
                              else "compile")
    assert by_name["setup_cache_hit_share"]["better"] == "higher"


DRIVER = """
import json, sys
sys.path.insert(0, {bench!r}); sys.path.insert(0, {root!r})
import run
from runners import serve
toy = {toy!r}
run.SPEC_PATH = toy + "/BENCHMARK.json"
run.CONFIG_DIR = toy + "/configs"
run.TRAFFIC_DIR = toy + "/traffic"
run.check_devices = lambda chips: None
inner = serve.run
def tapped(*a, **kw):
    import importlib
    result = inner(*a, **kw)
    ctx = {{"counters": result["counters"], "trace": None}}
    print("READERS " + json.dumps({{
        n: importlib.import_module("layer_metrics." + n).read(ctx)
        for n in {readers!r}}}), flush=True)
    return result
serve.run = tapped
rc = run.main(sys.argv[1:]); sys.stdout.flush(); import os; os._exit(rc)
"""


def test_a_toy_cell_reads_all_eight_cold_and_then_warm(tmp_path):
    """One cell twice on one compile cache (``JAX_COMPILATION_CACHE_DIR``
    at a fresh directory): the first run compiles everything, the
    second reads everything back.  Seconds here are the CPU's: what is
    held is that every reader has a value and what the shares say."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    code = DRIVER.format(bench=os.path.join(ROOT, "benchmarks"), root=ROOT,
                         toy=os.path.join(HERE, "toy"), readers=READERS)
    got = []
    for seed in ("2147483659", "2147483693"):
        out = subprocess.run(
            [sys.executable, "-c", code, "--workload", "serve-chat-open",
             "--seed", seed, "--seconds", "4", "--trace", "0"],
            capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
        lines = out.stdout.strip().splitlines()
        assert json.loads(lines[-1])["correct"] is True
        got.append(json.loads(next(
            ln for ln in lines if ln.startswith("READERS "))[8:]))
    cold, warm = got
    for run_ in got:
        assert all(run_[n] is not None for n in READERS), run_
        assert run_["serve_build_s_in_window"] == 0.0
        assert run_["setup_state_s"] > 0 and run_["setup_warm_run_s"] > 0
    assert cold["setup_cache_hit_share"] < 50.0
    assert warm["setup_cache_hit_share"] == 100.0
    assert warm["setup_programs_built"] == cold["setup_programs_built"]
    assert warm["setup_programs_built"] > 20
