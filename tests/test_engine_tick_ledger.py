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


def test_phases_tile_the_engine_thread(small):
    """Idle stretches, bursts, a chunked admission and a prefix hit:
    tick_s + idle_wait_s is the engine thread's wall time, and the
    phases account for the ticks."""
    cfg, params = small
    eng = _engine(cfg, params)
    try:
        # compiles out of the way: the marks must fall on an idle engine
        for p in _prompts(0, (5, 12, 20)):
            eng.submit(p, 4).result(timeout=120)
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
    assert d["ticks"] > 10 and d["idle_wait_s"] >= 0.4, d
    wall = t_b - t_a
    assert d["tick_s"] + d["idle_wait_s"] == pytest.approx(wall, rel=0.05)
    # the phases account for the ticks
    assert sum(d[k] for k in PHASE_KEYS) >= 0.95 * d["tick_s"], d
    assert sum(d[k] for k in PHASE_KEYS) <= d["tick_s"] * 1.0001, d
    assert coverage >= 0.95
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
    assert st["admitted"] == st["first_tokens"] == st["requests_done"] == 7
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

    def spy(name):
        names.add(name)
        return real(name)

    monkeypatch.setattr(obs_trace, "annotation", spy)
    eng = _engine(cfg, params)
    try:
        eng.submit(_prompts(8, (6,))[0], 4).result(timeout=120)
        eng.run_on_engine(lambda: None)
    finally:
        eng.stop()
    assert names == {f"engine/{p}" for p in engine_mod.TICK_PHASES}
