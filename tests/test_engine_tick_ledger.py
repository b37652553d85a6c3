"""The tick ledger inside ``ContinuousBatcher`` (ISSUE 24): the engine
thread's phases tile its life, every request is stamped at submit,
admission, first token and completion, and both reach ``stats()``, the
``edl_engine_*`` histograms and one ``engine/request`` trace event."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edl_tpu.models import TransformerConfig, TransformerLM
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.serving import ContinuousBatcher
from edl_tpu.serving import engine as engine_mod

PHASE_KEYS = ("tick_tasks_s", "tick_admit_s", "tick_dispatch_s",
              "tick_sync_s", "tick_finish_s", "tick_kv_commit_s")


@pytest.fixture(scope="module")
def small():
    cfg = TransformerConfig(vocab_size=97, num_layers=2, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=64,
                            remat=False, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("temperature", 0.0)
    kw.setdefault("steps_per_sync", 2)
    kw.setdefault("kv_block", 4)
    kw.setdefault("kv_pool_blocks", 64)
    kw.setdefault("prefill_chunk", 8)
    return ContinuousBatcher(cfg, params, **kw)


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 97, (n,)).astype(np.int32) for n in lens]


def _mark(eng):
    """An instant on the engine thread, and the counters once the tick
    that held it has closed (the engine is idle at both marks, so that
    tick is a few microseconds long)."""
    t = eng.run_on_engine(time.perf_counter)
    time.sleep(0.05)
    return t, eng.stats()


def test_phases_tile_the_engine_thread():
    """Idle stretches, bursts, a chunked admission and a prefix hit:
    tick_s + idle_wait_s is the engine thread's wall time, and the
    phases account for the ticks."""
    # a toy whose step program outlasts the host's turn, as on the chip:
    # with the reads a tick late (ISSUE 31) the host no longer waits out
    # the device in every tick, and the two-layer toy's 0.5 ms ticks
    # would measure the ledger's own 20-70 us a tick between phases (the
    # same before and after) and not the tiling
    cfg = TransformerConfig(vocab_size=97, num_layers=4, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=64,
                            remat=False, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    eng = _engine(cfg, params, steps_per_sync=8)
    try:
        # compiles out of the way: the marks must fall on an idle engine
        for p in _prompts(0, (5, 12, 20)):
            eng.submit(p, 4).result(timeout=120)
        # a group of two as well: its programs' compiles are seconds on a
        # loaded host, inside ONE tick's admit phase
        [f.result(timeout=120)
         for f in [eng.submit(p, 3) for p in _prompts(4, (6, 7, 5))]]
        t_a, s_a = _mark(eng)
        time.sleep(0.3)                                   # idle stretch
        futs = [eng.submit(p, 6) for p in _prompts(1, (3, 7, 5, 6, 4, 8))]
        [f.result(timeout=120) for f in futs]             # a burst
        long = _prompts(2, (21,))[0]                      # chunked: 8+8+5
        out = eng.submit(long, 5).result(timeout=120)
        again = np.concatenate([long, out, [3, 4]]).astype(np.int32)
        eng.submit(again, 3).result(timeout=120)          # a prefix hit
        time.sleep(0.2)                                   # idle again
        futs = [eng.submit(p, 5) for p in _prompts(3, (4, 9, 6, 2))]
        [f.result(timeout=120) for f in futs]
        time.sleep(0.05)
        # the ledger's own self-check, an EMA over ticks: read before
        # the mark's tick, which holds a few microseconds of work
        coverage = eng.stats()["tick_coverage"]
        t_b, s_b = _mark(eng)
    finally:
        eng.stop()
    d = {k: s_b[k] - s_a[k] for k in s_a
         if isinstance(s_a[k], (int, float)) and not isinstance(s_a[k], bool)}
    assert d["chunked_admissions"] >= 1 and d["kv_prefix_hits"] >= 1, d
    # the reads trail the dispatches by a tick (ISSUE 31) and the
    # phases still tile: sync and finish belong to the PREVIOUS tick's
    # programs, inside this tick's wall time
    assert 0 < d["lookahead_ticks"] < d["ticks"], d
    assert d["ticks"] > 10 and d["idle_wait_s"] >= 0.4, d
    wall = t_b - t_a
    assert d["tick_s"] + d["idle_wait_s"] == pytest.approx(wall, rel=0.05)
    # the phases account for the ticks
    assert sum(d[k] for k in PHASE_KEYS) >= 0.95 * d["tick_s"], d
    assert sum(d[k] for k in PHASE_KEYS) <= d["tick_s"] * 1.0001, d
    # the ledger's own self-check is an EMA over TICKS (weight 0.1): one
    # of the last ticks, half a millisecond long here, descheduled
    # between two phases on a loaded host reads 0.3 and takes the EMA
    # from 0.99 under 0.95.  The sums above are the tiling's judge (one
    # such tick moves them by a thousandth); the EMA is held to being
    # the same kind of number
    assert 0.75 <= coverage <= 1.0001
    # every phase that has work here saw some; kv_commit is nested in
    # finish and deducted from it, never counted twice
    for k in ("tick_admit_s", "tick_dispatch_s", "tick_sync_s",
              "tick_finish_s", "tick_kv_commit_s"):
        assert d[k] > 0, (k, d)
    # prefill_stall_s is a PART of admit (ticks with live lanes and an
    # admission), not another name for it
    assert d["prefill_stall_s"] <= d["tick_admit_s"] + 1e-3, d


def test_request_stages_ordered_and_counted(small, monkeypatch):
    cfg, params = small
    seen = []
    monkeypatch.setattr(ContinuousBatcher, "_emit_request",
                        staticmethod(lambda req, n_out: seen.append(req)))
    tap = lambda rec: None  # noqa: E731 — a tap makes trace.active() true
    obs_trace.add_tap(tap)
    eng = _engine(cfg, params, slots=2)
    try:
        t0 = time.monotonic()
        futs = [eng.submit(p, n) for p, n in zip(
            _prompts(4, (3, 7, 12, 5, 9, 21, 2)), (6, 1, 9, 4, 1, 5, 8))]
        outs = [f.result(timeout=120) for f in futs]
        assert eng.drain(timeout=60)
        t1 = time.monotonic()
        st = eng.stats()
    finally:
        obs_trace.remove_tap(tap)
        eng.stop()
    assert len(seen) == 7
    for req in seen:
        assert (t0 <= req.t_submit <= req.t_admit <= req.t_first
                <= req.t_done <= t1)
        # the ledger's record of the request tiles its life, and its
        # wait by cause tiles its queue wait (ISSUE 34)
        rec = {k: req.stage_s(k) for k in engine_mod._STAGE_STAMPS}
        assert rec["queue_wait"] + rec["prefill"] + rec["decode"] == \
            pytest.approx(req.t_done - req.t_submit, abs=1e-9)
        assert rec["ttft"] == pytest.approx(req.t_first - req.t_submit)
        assert sum(req.waits.values()) == pytest.approx(rec["queue_wait"])
        assert req.lane in engine_mod.LANES
    assert st["admitted"] == st["first_tokens"] == st["requests_done"] == 7
    assert sum(st[f"queue_wait_cause_{c}_s"]
               for c in engine_mod.WAIT_CAUSES) == pytest.approx(
        st["queue_wait_s_sum"], rel=1e-9)
    assert st["tokens_emitted"] == sum(len(o) for o in outs)
    assert st["decode_tokens"] == st["tokens_emitted"] - st["requests_done"]
    # the sums are the stamps' own differences
    assert st["queue_wait_s_sum"] == pytest.approx(
        sum(r.t_admit - r.t_submit for r in seen))
    assert st["ttft_s_sum"] == pytest.approx(
        sum(r.t_first - r.t_submit for r in seen))
    assert st["decode_s_sum"] == pytest.approx(
        sum(r.t_done - r.t_first for r in seen
            if len(r.future.result()) > 1))
    # more requests than slots: somebody waited for a slot
    assert st["queue_wait_s_sum"] > 0 and st["ttft_s_sum"] > 0


def test_engine_request_event_fields_and_prefix_skip(small, monkeypatch):
    from edl_tpu.obs import context as obs_context
    monkeypatch.setattr(obs_context, "_process_root", None)
    cfg, params = small
    events = []
    tap = lambda rec: events.append(rec)  # noqa: E731
    obs_trace.add_tap(tap)
    eng = _engine(cfg, params)
    try:
        p = _prompts(5, (13,))[0]
        out = eng.submit(p, 6).result(timeout=120)
        nxt = np.concatenate([p, out, [5]]).astype(np.int32)
        out2 = eng.submit(nxt, 3).result(timeout=120)
    finally:
        obs_trace.remove_tap(tap)
        eng.stop()
    reqs = [e for e in events if e["name"] == "engine/request"]
    assert len(reqs) == 2
    for e, n_prompt, n_out in zip(reqs, (13, len(nxt)), (6, len(out2))):
        assert e["n_prompt"] == n_prompt and e["n_out"] == n_out
        assert min(e["queue_wait"], e["prefill"], e["decode"]) >= 0
        assert e["queue_wait"] + e["prefill"] + e["decode"] == \
            pytest.approx(e["dur"], abs=1e-4)
        assert "trace_id" not in e        # nobody's trace was ambient
    assert reqs[0]["prefix_tokens_skipped"] == 0
    # the second turn extends the first's committed chain: 13 + 5
    # processed tokens = 4 full blocks of 4
    assert reqs[1]["prefix_tokens_skipped"] == 16


def test_null_tracer_builds_no_event(small, monkeypatch):
    """No tracer, no tap: one attribute test per request, nothing built."""
    cfg, params = small
    # whatever an earlier test of this worker left installed
    monkeypatch.setattr(obs_trace, "_TAPS", [])
    prev = obs_trace.install(obs_trace.NullTracer())
    built = []
    monkeypatch.setattr(ContinuousBatcher, "_emit_request",
                        staticmethod(lambda *a: built.append(a)))
    eng = _engine(cfg, params, kv_block=0)
    try:
        assert not obs_trace.active()
        eng.submit(_prompts(6, (5,))[0], 3).result(timeout=120)
    finally:
        eng.stop()
        obs_trace.install(prev)
    assert built == []


def _hist(name, **labels):
    child = obs_metrics.REGISTRY.get(name)
    return child.labels(**labels) if labels else child


def test_histograms_observe_at_the_event(small):
    cfg, params = small
    before = {
        "wait": _hist("edl_engine_queue_wait_seconds").count,
        "ttft": _hist("edl_engine_ttft_seconds").count,
        "gap": _hist("edl_engine_intertoken_seconds").count,
        **{p: _hist("edl_engine_tick_phase_seconds", phase=p).count
           for p in engine_mod.TICK_PHASES},
    }
    eng = _engine(cfg, params, kv_block=0)
    try:
        futs = [eng.submit(p, n) for p, n in zip(_prompts(7, (4, 6, 9)),
                                                  (5, 1, 3))]
        [f.result(timeout=120) for f in futs]
        _mark(eng)
        st = eng.stats()
    finally:
        eng.stop()
    assert _hist("edl_engine_queue_wait_seconds").count - before["wait"] == 3
    assert _hist("edl_engine_ttft_seconds").count - before["ttft"] == 3
    # a one-token answer has no gap between tokens
    assert _hist("edl_engine_intertoken_seconds").count - before["gap"] == 2
    for p in engine_mod.TICK_PHASES:   # one observation a phase a tick
        got = _hist("edl_engine_tick_phase_seconds", phase=p).count
        assert got - before[p] == st["ticks"], p


def test_engine_phases_are_profiler_annotations(small, monkeypatch):
    """Each phase is an ``engine/<phase>`` span for any capture."""
    cfg, params = small
    names = set()
    real = obs_trace.annotation

    def spy(name, **args):
        names.add(name)
        return real(name, **args)

    monkeypatch.setattr(obs_trace, "annotation", spy)
    eng = _engine(cfg, params)
    try:
        eng.submit(_prompts(8, (6,))[0], 4).result(timeout=120)
        eng.run_on_engine(lambda: None)
    finally:
        eng.stop()
    # beside them the program-build ledger's spans (ISSUE 50): the
    # engine's state and what its first request builds
    builds = {n for n in names if n.startswith(("build/", "setup/"))}
    assert names - builds == {f"engine/{p}" for p in engine_mod.TICK_PHASES}
    assert {"setup/engine/state", "build/engine/prefill",
            "build/engine/step", "build/kv/pool_commit"} <= builds


# -- the one-tick lookahead's counters (ISSUE 31) ----------------------------

def test_lookahead_counters_are_cumulative_and_bounded(small):
    """``lookahead_ticks`` counts ticks that enqueued programs behind an
    unread tick: every tick of a busy period but its first (nothing to
    look past) and its last (nothing left to enqueue)."""
    cfg, params = small
    eng = _engine(cfg, params, slots=1, kv_block=0, prefill_chunk=0)
    try:
        eng.submit(_prompts(9, (5,))[0], 2).result(timeout=120)  # compiles
        _, s0 = _mark(eng)
        # 1 token from the prefill + 12 from 6 step programs of 2: ticks
        # 1 (prefill), 2-7 (a step each, 6 behind an unread tick), 8
        # (the last read alone)
        eng.submit(_prompts(10, (6,))[0], 13).result(timeout=120)
        _, s1 = _mark(eng)
    finally:
        eng.stop()
    assert s1["lookahead_ticks"] - s0["lookahead_ticks"] == 6
    assert s1["lookahead_discarded_token_steps"] == 0
    # s1 also holds the tick of the first mark's closure
    assert s1["ticks"] - s0["ticks"] == 8 + 1


def test_a_chunk_only_tick_is_no_lookahead_tick(small):
    """No slot live, a long prompt prefilling one chunk a tick: those
    ticks enqueue a program and leave nothing to read, so neither they
    nor the tick after them count (a serial engine did not wait on
    them either: the counter is the mechanism's, not the queue's)."""
    cfg, params = small
    eng = _engine(cfg, params, slots=1, kv_block=0)       # chunks of 8
    try:
        eng.submit(_prompts(12, (30,))[0], 3).result(timeout=120)
        _, s0 = _mark(eng)
        # ticks: mid, mid, mid (8 + 8 + 8), final chunk (6) + insert,
        # one step of 2 (behind the unread insert), the last read
        eng.submit(_prompts(13, (30,))[0], 3).result(timeout=120)
        _, s1 = _mark(eng)
    finally:
        eng.stop()
    assert s1["prefill_chunks"] - s0["prefill_chunks"] == 4
    assert s1["ticks"] - s0["ticks"] == 6 + 1     # + the mark's own
    assert s1["lookahead_ticks"] - s0["lookahead_ticks"] == 1


def test_speculative_engine_reads_before_it_dispatches(small):
    """How far a round advances a slot is the device's answer, so the
    host cannot know the next tick's budgets: same loop, the read not
    deferred, ``lookahead_ticks`` stays 0."""
    from edl_tpu.models.generate import generate

    cfg, params = small
    eng = _engine(cfg, params, kv_block=0, prefill_chunk=0, spec_k=2,
                  steps_per_sync=6, draft_cfg=cfg, draft_params=params)
    reads = []
    real = eng._read

    def read(tick):
        reads.append(eng._inflight)
        real(tick)

    eng._read = read
    try:
        prompts = _prompts(11, (4, 9, 6, 3))
        futs = [eng.submit(p, 9) for p in prompts]
        outs = [f.result(timeout=120) for f in futs]
        st = eng.stats()
    finally:
        eng.stop()
    for p, out in zip(prompts, outs):
        want = np.asarray(generate(cfg, params, jnp.asarray(p[None]), 9,
                                   temperature=0.0))[0]
        np.testing.assert_array_equal(out, want)
    assert st["lookahead_ticks"] == 0 and st["ticks"] > 3
    assert st["lookahead_discarded_token_steps"] == 0
    assert reads and all(r is None for r in reads)    # nothing deferred


def _lookahead_share():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "layer_metrics",
        "engine_lookahead_share.py")
    spec = importlib.util.spec_from_file_location("_lookahead_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("counters,want", [
    ({"ticks": 200, "lookahead_ticks": 190}, 95.0),
    ({"ticks": 200, "lookahead_ticks": 0}, 0.0),
    ({"ticks": 200}, None),          # the parent: no such counter
    ({"ticks": 0, "lookahead_ticks": 0}, None),
])
def test_engine_lookahead_share_reader(counters, want):
    got = _lookahead_share()({"counters": counters})
    assert got == want if want is None else got == pytest.approx(want)


def test_engine_lookahead_share_is_declared_for_the_serve_cells():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(m for m in spec["per_layer"]
                 if m["name"] == "engine_lookahead_share")
    # a later cell may be appended to its list: the four are held, the
    # list's end is not
    cells = entry.pop("workloads")
    assert entry == {
        "name": "engine_lookahead_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine tick",
        "moves": "serve_tokens_per_s"}
    assert cells[:4] == ["serve-chat-open", "serve-doc-sessions",
                         "serve-moe-decode-open", "serve-hybrid-mixed-open"]
    # and every serve cell the file has since, in its order
    assert cells == next(m["workloads"] for m in spec["end_to_end"]
                         if m["name"] == "serve_tokens_per_s")
    assert cells[5:7] == ["serve-latent-reason-open", "serve-mla-docs-closed"]
    layers = {m["layer"] for m in spec["per_layer"]
              if m["name"] != "engine_lookahead_share"}
    assert entry["layer"] in layers       # a layer the file already names
