"""The readers of the engine's tick ledger (PR 24): each on hand-made
counters, ``serve_idle_unattributed_share`` on the recorded v5e xplane,
and one toy ``serve-chat-open`` run showing that the new ``stats()``
keys reach ``ctx["counters"]`` through ``runners/serve.py`` as it was."""
import importlib
import json
import os
import subprocess
import sys

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data", "small_tpu.xplane.pb")

# a 45 s window: 500 ticks of 80 ms, 5 s with nothing to do
COUNTERS = {
    "window_s": 45.0, "ticks": 500, "tick_s": 40.0, "idle_wait_s": 5.0,
    "tick_tasks_s": 0.0, "tick_admit_s": 2.5, "tick_dispatch_s": 6.0,
    "tick_sync_s": 22.0, "tick_finish_s": 7.0, "tick_kv_commit_s": 1.5,
    "tick_coverage": 0.0, "admitted": 94, "queue_wait_s_sum": 4.7,
    "first_tokens": 94, "ttft_s_sum": 28.2, "decode_tokens": 9000,
    "decode_s_sum": 180.0,
}
WANT = {
    "engine_tick_ms": 80.0,
    "engine_tick_host_share": 45.0,
    "engine_admit_ms_per_tick": 5.0,
    "engine_finish_ms_per_tick": 17.0,
    "engine_idle_wait_share": 100.0 * 5.0 / 45.0,
    "engine_queue_wait_mean_s": 0.05,
    "engine_ttft_mean_s": 0.3,
    "engine_intertoken_gap_ms": 20.0,
}


def reader(name):
    return importlib.import_module(f"layer_metrics.{name}").read


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_hand_made_counters(name):
    assert reader(name)({"counters": dict(COUNTERS), "trace": None}) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_says_nothing_for_an_engine_without_the_ledger(name):
    """The parent commit's engine has none of these keys: the reader
    returns None and the line leaves the metric out; it never raises."""
    old = {"window_s": 45.0, "prefill_stall_s": 0.2, "requests_done": 94,
           "tokens_emitted": 9000}
    assert reader(name)({"counters": old, "trace": None}) is None
    # a window in which nothing ticked, was admitted or was finished
    idle = dict.fromkeys(COUNTERS, 0) | {"window_s": 45.0}
    got = reader(name)({"counters": idle, "trace": None})
    assert got is None or got == 0.0


def test_unattributed_share_of_idle_on_the_recorded_trace():
    read = reader("serve_idle_unattributed_share")
    assert read({"counters": {}, "trace": None}) is None
    assert read({"counters": {}, "trace": {"idle_gaps": {}}}) is None
    tr = trace_reduce.reduce_xplane(DATA)
    gaps = tr["idle_gaps"]
    want = 100.0 * gaps.get("host:unattributed", 0.0) / sum(gaps.values())
    got = read({"counters": {}, "trace": tr})
    assert got == pytest.approx(want)
    # the recording naps under a bench/nap span: most of its idle time
    # has an owner
    assert 0.0 <= got < 50.0
    assert read({"counters": {}, "trace": {"idle_gaps": {
        "host:unattributed": 3.0, "host:engine_sync": 1.0}}}) == 75.0


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
    result = inner(*a, **kw)
    c = {{k: v for k, v in result["counters"].items()
         if isinstance(v, (int, float))}}
    print("COUNTERS " + json.dumps(c), flush=True)
    return result
serve.run = tapped
rc = run.main(sys.argv[1:]); sys.stdout.flush(); import os; os._exit(rc)
"""


def test_new_counters_reach_the_readers_through_the_old_runner(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    code = DRIVER.format(bench=os.path.join(ROOT, "benchmarks"), root=ROOT,
                         toy=os.path.join(HERE, "toy"))
    out = subprocess.run(
        [sys.executable, "-c", code, "--workload", "serve-chat-open",
         "--seed", "2147483659", "--seconds", "4", "--trace", "0"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    counters = json.loads(next(
        ln for ln in lines if ln.startswith("COUNTERS "))[len("COUNTERS "):])
    assert set(COUNTERS) <= set(counters)
    assert counters["ticks"] > 0 and counters["admitted"] > 0
    # the engine thread's life over the window: ticks and waits
    assert counters["tick_s"] + counters["idle_wait_s"] == pytest.approx(
        counters["window_s"], rel=0.05)
    ctx = {"counters": counters, "trace": None}
    for name in WANT:
        assert reader(name)(ctx) is not None, name
    assert reader("engine_tick_ms")(ctx) > 0
    assert 0 < reader("engine_tick_host_share")(ctx) <= 100
