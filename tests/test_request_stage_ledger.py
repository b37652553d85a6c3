"""The request-stage ledger (ISSUE 34): every request is admitted down
a lane, its life is three stages that tile it, its queue wait is
charged tick by tick to the cause that kept the queue's head waiting,
the device's queue is counted at every enqueue, the replica stamps the
answer's way out, and all of it reaches ``stats()`` as flat cumulative
keys whose differences give means and percentiles."""

import importlib
import json
import math
import os
import sys
import threading
import time
from concurrent.futures import Future
from contextlib import contextmanager

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edl_tpu.models import TransformerConfig, TransformerLM
from edl_tpu.obs import trace as obs_trace
from edl_tpu.obs.ledger import STAGE_EDGES, RequestStageLedger
from edl_tpu.serving import ContinuousBatcher
from edl_tpu.serving import engine as engine_mod
from edl_tpu.serving.replica import ReplicaServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the readers import each other as the harness does (run.py has
# benchmarks/ first on its path); last here, so it shadows nothing
sys.path.append(os.path.join(ROOT, "benchmarks"))
CAUSE_KEYS = tuple(f"queue_wait_cause_{c}_s" for c in engine_mod.WAIT_CAUSES)


def _serve_cells():
    """The serve cells as ``BENCHMARK.json`` has them, in its order:
    every cell that reports ``serve_tokens_per_s``, and of them the open
    loops, which are the ones that report ``serve_latency_p50_s``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        ends = {m["name"]: m.get("workloads", [])
                for m in json.load(f)["end_to_end"]}
    return (list(ends["serve_tokens_per_s"]),
            list(ends["serve_latency_p50_s"]))


SERVE_CELLS, OPEN_CELLS = _serve_cells()


# -- the ledger alone ---------------------------------------------------------

def test_stage_edges_are_one_geometric_ladder():
    assert len(STAGE_EDGES) == 27 and STAGE_EDGES[-1] == math.inf
    assert STAGE_EDGES[0] == 0.002 and 45 < STAGE_EDGES[-2] < 60
    ratios = [b / a for a, b in zip(STAGE_EDGES, STAGE_EDGES[1:-1])]
    assert all(1.45 < r < 1.55 for r in ratios)


class _Hist:
    def __init__(self):
        self.seen = []

    def observe(self, v):
        self.seen.append(v)


def test_ledger_totals_are_flat_cumulative_and_there_from_birth():
    led = RequestStageLedger(
        {"queue_wait": ("cold", "chunk"), "prefill": ("cold", "chunk"),
         "decode": ()}, tails={"queue_wait": _Hist()})
    born = led.totals()
    assert set(born.values()) == {0}
    for x, lane in ((0.001, "cold"), (0.002, "cold"), (0.0021, "chunk"),
                    (0.5, "cold"), (0.7, None), (100.0, "chunk")):
        led.observe("queue_wait", x, lane)
    with pytest.raises(KeyError):
        led.observe("nobody's stage", 1.0)    # a typo must not vanish
    with pytest.raises(KeyError):
        led.observe("queue_wait", 1.0, "nobody's lane")
    with pytest.raises(KeyError):
        led.observe("decode", 1.0, "cold")    # a stage built without lanes
    tot = led.totals()
    assert set(tot) == set(born)              # a differencing reader's need
    assert all(isinstance(v, (int, float)) for v in tot.values())
    assert tot["stage_queue_wait_n"] == 6
    assert tot["stage_queue_wait_sum_s"] == pytest.approx(101.2051)
    assert tot["stage_queue_wait_cold_n"] == 3
    assert tot["stage_queue_wait_chunk_sum_s"] == pytest.approx(100.0021)
    ladder = [tot[f"stage_queue_wait_le_{'inf' if e == math.inf else f'{e:g}'}"]
              for e in STAGE_EDGES]
    # cumulative, an edge holds what is at or under it, the last holds all
    assert ladder == sorted(ladder)
    assert ladder[0] == 2 and ladder[1] == 3 and ladder[-2] == 5
    assert ladder[-1] == tot["stage_queue_wait_n"]
    # the ladder is for the stages whose tails are read, and no other
    assert tot["stage_prefill_n"] == tot["stage_decode_n"] == 0
    assert not any(k.startswith(("stage_prefill_le_", "stage_decode_le_"))
                   for k in tot)


def test_ledger_feeds_the_histograms_it_was_given():
    hist = _Hist()
    led = RequestStageLedger({"ttft": (), "decode": ()}, tails={"ttft": hist})
    led.observe("ttft", 0.25)
    led.observe("decode", 0.004)   # no tails: a sum and a count alone
    led.observe("ttft", -1e-9)     # a clock's jitter is no negative wait
    assert hist.seen == [0.25, 0.0]
    tot = led.totals()
    assert tot["stage_ttft_le_inf"] == 2 and tot["stage_decode_n"] == 1


# -- the engine ---------------------------------------------------------------

@pytest.fixture(scope="module")
def small():
    cfg = TransformerConfig(vocab_size=97, num_layers=2, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=64,
                            remat=False, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return cfg, params


@pytest.fixture(scope="module")
def eng(small):
    """One toy engine for every scenario: 3 slots (cold groups of at
    most 2), buckets 4 / 8 / 16, chunks of 8, a paged pool."""
    cfg, params = small
    engine = ContinuousBatcher(
        cfg, params, slots=3, prefill_buckets=(4, 8, 16), temperature=0.0,
        steps_per_sync=2, kv_block=4, kv_pool_blocks=64, prefill_chunk=8)
    try:
        # compiles out of the way
        for n in (3, 7, 30):
            engine.submit(_prompt(90 + n, n), 4).result(timeout=120)
        # and those of a GROUP of two (its prefill, its insert, the
        # lists' own tiny programs): the tests below compare one cause's
        # wait with another's, a tick against a few ticks, and a compile
        # that lands inside either is a hundred ticks long.  Which test
        # met a group first, and paid for it, depended on how the tests
        # fell to this worker
        for n in (3, 7):
            with _held(engine):
                pair = [engine.submit(_prompt(80 + n + i, n), 3)
                        for i in range(2)]
            [f.result(timeout=120) for f in pair]
        yield engine
    finally:
        engine.stop()


@pytest.fixture
def events():
    """The ``engine/request`` events of one test, by prompt length."""
    got = []
    tap = lambda rec: got.append(rec)  # noqa: E731
    obs_trace.add_tap(tap)
    try:
        yield lambda: {e["n_prompt"]: e for e in got
                       if e["name"] == "engine/request"}
    finally:
        obs_trace.remove_tap(tap)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 97, (n,)).astype(np.int32)


@contextmanager
def _held(engine):
    """What is submitted inside reaches ONE ``_admit`` together: the
    engine thread is busy with a task until the block ends."""
    started, release = threading.Event(), threading.Event()

    def task():
        started.set()
        release.wait(25)

    th = threading.Thread(target=engine.run_on_engine, args=(task,))
    th.start()
    assert started.wait(30)
    try:
        yield
        time.sleep(0.02)       # the wait they are charged to the tick
    finally:
        release.set()
        th.join(30)


def _waits(event):
    return {c: event.get(f"wait_{c}", 0.0) for c in engine_mod.WAIT_CAUSES}


def _delta(a, b, keys):
    return {k: b[k] - a[k] for k in keys}


def test_a_lone_arrival_waits_for_the_tick_alone(eng, events):
    s0 = eng.stats()
    eng.submit(_prompt(1, 5), 3).result(timeout=120)
    e = events()[5]
    assert e["lane"] == "cold" and e["chunks"] == 0
    assert set(k for k in e if k.startswith("wait_")) == {"wait_tick"}
    assert e["wait_tick"] == pytest.approx(e["queue_wait"], abs=1e-5)
    d = _delta(s0, eng.stats(), CAUSE_KEYS)
    assert d["queue_wait_cause_tick_s"] > 0
    assert sum(d.values()) == pytest.approx(d["queue_wait_cause_tick_s"])


def test_a_short_prompt_under_a_long_one_waits_for_the_lane(eng, events):
    s0 = eng.stats()
    with _held(eng):
        long = eng.submit(_prompt(2, 30), 3)     # 8 + 8 + 8 + 6: 4 chunks
        short = eng.submit(_prompt(3, 6), 3)
    long.result(timeout=120), short.result(timeout=120)
    ev = events()
    assert ev[30]["lane"] == "chunk" and ev[30]["chunks"] == 4
    assert ev[6]["lane"] == "cold"
    w = _waits(ev[6])
    # slots were free all along: the lane alone kept it, for the ticks
    # of the long prompt's four chunks; before that it waited out the
    # task, charged to the tick
    assert w["lane"] > 0 and w["slots"] == 0 and w["group"] == 0
    assert sum(w.values()) == pytest.approx(ev[6]["queue_wait"], abs=1e-5)
    assert _waits(ev[30])["lane"] == 0
    s1 = eng.stats()
    d = _delta(s0, s1, CAUSE_KEYS + (
        "chunk_lane_busy_s", "chunked_admissions", "stage_prefill_chunk_n",
        "stage_prefill_chunk_sum_s", "stage_queue_wait_cold_n"))
    assert d["queue_wait_cause_lane_s"] == pytest.approx(w["lane"], abs=1e-5)
    assert d["chunked_admissions"] == d["stage_prefill_chunk_n"] == 1
    assert d["stage_queue_wait_cold_n"] == 1
    # the lane is held from the admission to the last chunk's dispatch:
    # no longer than the request's prefill, which ends at the read
    assert 0 < d["chunk_lane_busy_s"] <= d["stage_prefill_chunk_sum_s"]
    assert d["stage_prefill_chunk_sum_s"] == pytest.approx(
        ev[30]["prefill"], abs=1e-5)


def test_a_long_prompt_behind_two_waits_for_the_lane_too(eng, events):
    """The lane holds two long prompts (PR 54); the third finds it full
    and waits for it, slots free."""
    with _held(eng):
        futs = [eng.submit(_prompt(4, 29), 2), eng.submit(_prompt(5, 27), 2),
                eng.submit(_prompt(6, 25), 2)]
    [f.result(timeout=120) for f in futs]
    ev = events()
    assert ev[29]["lane"] == ev[27]["lane"] == ev[25]["lane"] == "chunk"
    assert _waits(ev[29])["lane"] == _waits(ev[27])["lane"] == 0
    w = _waits(ev[25])
    assert w["lane"] > 0 and w["slots"] == 0


def test_more_requests_than_slots_wait_for_slots(eng, events):
    s0 = eng.stats()
    with _held(eng):
        futs = [eng.submit(_prompt(10 + i, 5 + i % 2), 14) for i in range(5)]
        last = eng.submit(_prompt(20, 8), 2)
    [f.result(timeout=120) for f in futs + [last]]
    w = _waits(events()[8])
    # three slots, six requests: the last waits for a generation to end
    assert w["slots"] > 0 and w["slots"] > w["group"] + w["lane"]
    d = _delta(s0, eng.stats(), CAUSE_KEYS + ("queue_wait_s_sum", "admitted"))
    assert d["admitted"] == 6 and d["queue_wait_cause_slots_s"] >= w["slots"]
    assert sum(d[k] for k in CAUSE_KEYS) == pytest.approx(
        d["queue_wait_s_sum"], rel=1e-9)


def test_two_buckets_in_one_burst_wait_for_the_group(eng, events):
    s0 = eng.stats()
    with _held(eng):
        a = eng.submit(_prompt(6, 3), 2)          # bucket 4
        b = eng.submit(_prompt(7, 7), 2)          # bucket 8
    a.result(timeout=120), b.result(timeout=120)
    ev = events()
    assert ev[3]["lane"] == ev[7]["lane"] == "cold"
    assert _waits(ev[3])["group"] == 0
    w = _waits(ev[7])
    # one cold group a tick, and it took the other bucket
    assert w["group"] > 0 and w["slots"] == 0 and w["lane"] == 0
    d = _delta(s0, eng.stats(), CAUSE_KEYS)
    assert d["queue_wait_cause_group_s"] == pytest.approx(w["group"],
                                                          abs=1e-5)


def test_the_lane_cap_of_a_group_is_the_group_too(eng, events):
    # three of one bucket, three free slots, groups of at most two
    assert eng.PREFILL_KS[0] == 2
    with _held(eng):
        futs = [eng.submit(_prompt(30 + n, n), 2) for n in (5, 6, 7)]
    [f.result(timeout=120) for f in futs]
    ev = events()
    assert _waits(ev[5])["group"] == _waits(ev[6])["group"] == 0
    assert _waits(ev[7])["group"] > 0


def test_a_prefix_hit_takes_the_reuse_lane(eng, events):
    p = _prompt(8, 13)
    out = eng.submit(p, 6).result(timeout=120)
    nxt = np.concatenate([p, out, [5]]).astype(np.int32)
    s0 = eng.stats()
    eng.submit(nxt, 3).result(timeout=120)
    ev = events()
    assert ev[13]["lane"] == "chunk" and ev[len(nxt)]["lane"] == "reuse"
    assert ev[len(nxt)]["prefix_tokens_skipped"] == 16
    s1 = eng.stats()
    d = _delta(s0, s1, ("stage_queue_wait_reuse_n", "stage_prefill_reuse_n",
                        "stage_decode_n", "stage_ttft_n"))
    assert set(d.values()) == {1}
    # the lane splits the stages whose service time it decides, no other
    assert not any(k.startswith(("stage_decode_reuse", "stage_ttft_reuse"))
                   for k in s1)


def test_stages_tile_a_request_and_the_causes_its_wait(eng, events):
    """Whatever mix: per request queue_wait + prefill + decode is its
    life and the causes its queue wait; in ``stats()`` the same sums,
    and every ladder ends at its count."""
    s0 = eng.stats()
    t0 = time.monotonic()
    with _held(eng):
        futs = [eng.submit(_prompt(40 + i, n), new) for i, (n, new) in
                enumerate(((3, 6), (7, 1), (12, 9), (5, 4), (9, 1),
                           (21, 5), (2, 8)))]
    outs = [f.result(timeout=120) for f in futs]
    wall = time.monotonic() - t0
    time.sleep(0.05)
    s1 = eng.stats()
    ev = events()
    assert len(ev) == 7
    for e in ev.values():
        assert e["queue_wait"] + e["prefill"] + e["decode"] == \
            pytest.approx(e["dur"], abs=1e-5)
        assert sum(_waits(e).values()) == pytest.approx(e["queue_wait"],
                                                        abs=1e-5)
        assert 0 < e["dur"] <= wall
        assert e["lane"] in engine_mod.LANES
    d = {k: s1[k] - s0[k] for k in s0
         if isinstance(s0[k], (int, float)) and not isinstance(s0[k], bool)}
    assert d["admitted"] == d["first_tokens"] == d["requests_done"] == 7
    assert d["tokens_emitted"] == sum(len(o) for o in outs)
    assert sum(d[k] for k in CAUSE_KEYS) == pytest.approx(
        d["queue_wait_s_sum"], rel=1e-9)
    # the old pairs ARE the ledger's
    assert d["stage_queue_wait_sum_s"] == d["queue_wait_s_sum"]
    assert d["stage_ttft_sum_s"] == d["ttft_s_sum"]
    assert d["stage_ttft_sum_s"] == pytest.approx(
        d["stage_queue_wait_sum_s"] + d["stage_prefill_sum_s"])
    assert d["stage_queue_wait_sum_s"] + d["stage_prefill_sum_s"] + \
        d["stage_decode_sum_s"] == pytest.approx(
            sum(e["dur"] for e in ev.values()), abs=1e-4)
    # decode_s_sum leaves one-token answers out, the stage does not
    assert d["decode_s_sum"] <= d["stage_decode_sum_s"]
    for stage in ("queue_wait", "prefill", "decode", "ttft"):
        assert d[f"stage_{stage}_n"] == 7
    for stage in ("queue_wait", "prefill"):       # the stages with lanes
        assert sum(d[f"stage_{stage}_{ln}_n"]
                   for ln in engine_mod.LANES) == 7
    for stage in ("queue_wait", "ttft"):          # the stages with tails
        ladder = [d[f"stage_{stage}_le_{'inf' if e == math.inf else f'{e:g}'}"]
                  for e in STAGE_EDGES]
        assert ladder == sorted(ladder) and ladder[-1] == 7
    assert d["stage_deliver_n"] == 0          # nobody released anything


def test_device_queue_counts_what_is_enqueued_ahead_of_a_read(small):
    """Decoding one request, the host stays a program or two ahead; a
    long prompt with no slot live enqueues chunk after chunk and reads
    nothing: the run-ahead the counter is for."""
    cfg, params = small
    eng = ContinuousBatcher(cfg, params, slots=1, prefill_buckets=(8, 16),
                            temperature=0.0, steps_per_sync=2, kv_block=0,
                            prefill_chunk=8)
    keys = ("device_enqueues", "device_queue_programs_sum")
    try:
        eng.submit(_prompt(50, 5), 3).result(timeout=120)
        eng.submit(_prompt(51, 30), 3).result(timeout=120)      # compiles
        time.sleep(0.05)
        s0 = eng.stats()
        # prefill, insert, then 6 steps of 2: the first step finds the
        # prefill and the insert unread, the others a step
        eng.submit(_prompt(52, 6), 13).result(timeout=120)
        time.sleep(0.05)
        s1 = eng.stats()
        # four chunks, the insert and a step with nothing read between
        eng.submit(_prompt(53, 30), 3).result(timeout=120)
        time.sleep(0.05)
        s2 = eng.stats()
    finally:
        eng.stop()
    plain, chunked = _delta(s0, s1, keys), _delta(s1, s2, keys)
    assert plain == {"device_enqueues": 8, "device_queue_programs_sum":
                     0 + 1 + 2 + 2 + 1 * 4}
    assert chunked == {"device_enqueues": 6, "device_queue_programs_sum":
                       0 + 1 + 2 + 3 + 4 + 5}


def test_admit_and_dispatch_spans_carry_their_arguments(eng, monkeypatch):
    seen = {}
    real = obs_trace.annotation

    def spy(name, **args):
        if args:
            seen.setdefault(name, []).append(args)
        return real(name, **args)

    monkeypatch.setattr(obs_trace, "annotation", spy)
    with _held(eng):
        futs = [eng.submit(_prompt(60, 20), 2), eng.submit(_prompt(61, 4), 2)]
    [f.result(timeout=120) for f in futs]
    assert set(seen) == {"engine/admit", "engine/dispatch"}
    admits = seen["engine/admit"]
    assert all(set(a) == {"pending", "cause", "lane_offset"} for a in admits)
    # the head's own cause as the tick begins, none with nobody pending
    assert {a["cause"] for a in admits} <= {*engine_mod.WAIT_CAUSES, "none"}
    assert all((a["cause"] == "none") == (a["pending"] == 0) for a in admits)
    assert any(a["pending"] >= 1 and a["cause"] == "lane" for a in admits)
    assert {a["lane_offset"] for a in admits} >= {-1, 8, 16}
    assert all(set(a) == {"ahead"} and a["ahead"] >= 0
               for a in seen["engine/dispatch"])


# -- deliver: the replica's two stamps ----------------------------------------

class _Engine:
    """What ``ReplicaServer`` touches, and ``observe_stage``."""

    def __init__(self):
        self.futures: list[Future] = []
        self.observed: list[tuple[str, float]] = []

    def submit(self, ids, max_new, **_kw):
        self.futures.append(Future())
        return self.futures[-1]

    def observe_stage(self, stage, seconds):
        self.observed.append((stage, seconds))

    def stats(self):
        return {"slots": 2, "active_slots": 0, "queue_depth": 0,
                "prefill_stall_s": 0.0, "tokens_per_s": 0.0,
                "max_prompt_len": 63}

    def stop(self):
        pass


@pytest.fixture
def replica(memkv):
    eng = _Engine()
    srv = ReplicaServer(memkv, "job", eng, replica_id="r0", host="127.0.0.1",
                        ttl=5.0, advert_period=0.2)
    try:
        yield eng, srv
    finally:
        srv.close()


def test_deliver_is_observed_once_a_released_request(replica):
    eng, srv = replica
    srv.serve_submit("q1", [7], 4)
    assert not srv.serve_wait("q1", timeout=0.01)["done"]
    eng.futures[0].set_result(np.arange(4, dtype=np.int32))
    time.sleep(0.05)                       # the answer waits to be taken
    got = srv.serve_wait("q1", timeout=1.0)
    assert got == {"done": True, "nbytes": 16}
    assert len(srv.serve_fetch("q1", 0, 16)) == 16
    assert eng.observed == []
    srv.serve_release("q1")
    (stage, seconds), = eng.observed
    assert stage == "deliver" and 0.05 <= seconds < 5.0
    srv.serve_release("q1")                # a transport retry of the ack
    assert len(eng.observed) == 1


def test_deliver_is_never_observed_for_a_hedge_loser(replica):
    eng, srv = replica
    srv.serve_submit("q2", [7], 4)
    srv.serve_release("q2")                # cancelled mid-generation
    eng.futures[0].set_result(np.arange(4, dtype=np.int32))
    time.sleep(0.05)
    assert eng.observed == []
    # and not for an answer that resolved and was never waited for
    srv.serve_submit("q3", [7], 4)
    eng.futures[1].set_result(np.arange(4, dtype=np.int32))
    srv.serve_release("q3")
    assert eng.observed == []


def test_observe_stage_reaches_stats(eng):
    s0 = eng.stats()
    eng.observe_stage("deliver", 0.004)
    d = _delta(s0, eng.stats(), ("stage_deliver_n", "stage_deliver_sum_s"))
    assert d == {"stage_deliver_n": 1,
                 "stage_deliver_sum_s": pytest.approx(0.004)}
    with pytest.raises(KeyError):
        eng.observe_stage("nobody's stage", 0.004)


# -- the readers --------------------------------------------------------------

def _reader(name):
    return importlib.import_module(f"layer_metrics.{name}").read


def _ladder(stage, at):
    """Differenced ladder keys of ``stage``: ``at`` maps an edge to the
    observations at or under it (edges between repeat the one below)."""
    out, acc = {}, 0
    for e in STAGE_EDGES:
        acc = max(acc, max((n for edge, n in at.items() if edge <= e),
                           default=0))
        out[f"stage_{stage}_le_{'inf' if e == math.inf else f'{e:g}'}"] = acc
    return out


# a window of 40 admissions: 30 cold, 8 through the lane, 2 hits
COUNTERS = {
    "window_s": 45.0, "admitted": 40, "queue_wait_s_sum": 20.0,
    "queue_wait_cause_slots_s": 1.0, "queue_wait_cause_lane_s": 12.0,
    "queue_wait_cause_group_s": 2.0, "queue_wait_cause_tick_s": 5.0,
    "stage_queue_wait_cold_sum_s": 9.0, "stage_queue_wait_cold_n": 30,
    "stage_prefill_chunk_sum_s": 12.0, "stage_prefill_chunk_n": 8,
    "device_enqueues": 2000, "device_queue_programs_sum": 3000,
    "stage_deliver_sum_s": 0.12, "stage_deliver_n": 40,
    # 20 at or under 0.0101 s, 30 under 0.389, 38 under 1.31, all under 2.96
    **_ladder("queue_wait", {0.0101: 20, 0.389: 30, 1.31: 38, 2.96: 40}),
    **_ladder("ttft", {0.0513: 10, 0.584: 36, 0.876: 40}),
}
WANT = {
    # rank 36 of 40: 6 of the 8 in (0.876, 1.31], log-linear
    "engine_queue_wait_p90_s": 0.876 * (1.31 / 0.876) ** 0.75,
    # rank 36 of 40: the last of the bucket (0.389, 0.584]: its edge
    "engine_ttft_p90_s": 0.584,
    "engine_queue_wait_lane_share": 60.0,
    "engine_queue_wait_slots_share": 5.0,
    "engine_cold_queue_wait_mean_s": 0.3,
    "engine_chunk_lane_prefill_mean_s": 1.5,
    "engine_device_queue_mean": 1.5,
    "replica_deliver_mean_s": 0.003,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_stage_reader_on_hand_made_counters(name):
    assert _reader(name)({"counters": dict(COUNTERS)}) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_stage_reader_says_nothing_without_the_ledger(name):
    """The parent commit's engine has none of these keys: None, never an
    exception; and a window in which nothing was counted reads None."""
    parent = {"window_s": 45.0, "admitted": 40, "queue_wait_s_sum": 20.0,
              "first_tokens": 40, "ttft_s_sum": 30.0, "ticks": 500}
    assert _reader(name)({"counters": parent}) is None
    idle = dict.fromkeys(COUNTERS, 0) | {"window_s": 45.0}
    assert _reader(name)({"counters": idle}) is None


def test_a_cause_that_cost_nothing_reads_zero_not_none():
    c = dict(COUNTERS, queue_wait_cause_slots_s=0.0)
    assert _reader("engine_queue_wait_slots_share")({"counters": c}) == 0.0


def test_ladder_percentile_ends():
    read = _reader("engine_queue_wait_p90_s")
    # all in the first bucket: it starts one ratio under its edge
    got = read({"counters": _ladder("queue_wait", {0.002: 10})})
    assert 0.002 / 1.5 < got <= 0.002
    # the rank beyond the last finite edge: that edge, a lower bound
    got = read({"counters": _ladder("queue_wait", {0.002: 1, math.inf: 10})})
    assert got == 50.5


@pytest.mark.parametrize("name,unit,layer,moves,cells", [
    ("engine_queue_wait_p90_s", "s", "replica and engine queue",
     "serve_tokens_per_s", SERVE_CELLS),
    ("engine_ttft_p90_s", "s", "engine tick", "serve_tokens_per_s",
     SERVE_CELLS),
    ("engine_queue_wait_lane_share", "%", "replica and engine queue",
     "serve_tokens_per_s", SERVE_CELLS),
    ("engine_queue_wait_slots_share", "%", "replica and engine queue",
     "serve_tokens_per_s", SERVE_CELLS),
    ("engine_cold_queue_wait_mean_s", "s", "replica and engine queue",
     "serve_latency_p50_s", OPEN_CELLS),
    ("engine_chunk_lane_prefill_mean_s", "s", "engine tick",
     "serve_tokens_per_s", SERVE_CELLS),
    ("engine_device_queue_mean", "programs", "engine tick",
     "serve_tokens_per_s", SERVE_CELLS),
    ("replica_deliver_mean_s", "s", "gateway", "serve_latency_p50_s",
     OPEN_CELLS),
])
def test_stage_reader_is_declared_for_its_cells(name, unit, layer, moves,
                                                cells):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    listed = entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": layer,
                     "moves": moves}
    # every serve cell the file has, in its order: a cell that a later
    # PR adds is held from the day it is appended
    assert listed == cells
    moved = next(m for m in spec["end_to_end"] if m["name"] == moves)
    assert set(listed) <= set(moved["workloads"])
    assert layer in {m["layer"] for m in spec["per_layer"]
                     if m["name"] != name}


@pytest.mark.parametrize("cell,loop", [
    ("serve-chat-open", "open"), ("serve-doc-sessions", "closed"),
    ("serve-moe-decode-open", "open"), ("serve-hybrid-mixed-open", "open"),
    ("serve-ssm-chat-open", "open"), ("serve-latent-reason-open", "open"),
    ("serve-mla-docs-closed", "closed")])
def test_a_serve_cell_is_held_by_the_stage_readers(cell, loop):
    """The lists above are read from the file: each cell it has today is
    among them, an open loop among the latency readers' too."""
    assert cell in SERVE_CELLS
    assert (cell in OPEN_CELLS) == (loop == "open")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    traffic = next(w["traffic"] for w in spec["workloads"]
                   if w["name"] == cell)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           f"{traffic}.json")) as f:
        assert json.load(f)["loop"] == loop
