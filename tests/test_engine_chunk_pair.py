"""The chunk lane holds two long prompts at once (ISSUE 54): each has
its own one-lane slab and advances by one chunk a tick through the
programs a prompt alone runs, the second joins whenever it reaches the
queue's front, and nothing about an answer changes.

Parity first (greedy tokens equal to ``models.generate`` of each prompt
alone), mechanism second (``chunk_pair_dispatches`` counts the ticks in
which the lane advanced two prompts, ``prefill_chunks`` the chunks it
dispatched in all).  The prompts of a case reach the queue from ONE
task on the engine thread, so which of them the lane finds waiting is
the script's, never the scheduler's.
"""

import importlib.util
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models import TransformerConfig, TransformerLM
from edl_tpu.models.generate import generate
from edl_tpu.parallel import MeshSpec, build_mesh
from edl_tpu.serving import ContinuousBatcher
from tests.test_engine_model_counters import CONFIGS

LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
GQA = TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                        num_heads=4, num_kv_heads=2, mlp_dim=64, max_len=96,
                        remat=False, dtype=jnp.float32)
STACKS = {
    "gqa": GQA,
    # window x 3 then global: rings and their snapshots
    "window": CONFIGS["exaone_window_held"],
    # ssm, ssm, global, ssm: SsmState beside head rows
    "ssm_state": CONFIGS["granite_ssm"],
    # kda x 3 then latent: KdaState and the expanded latent path
    "kda_latent": CONFIGS["kimi_kda_latent"],
}
C = 16


def _params(cfg):
    return TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]


def _engine(cfg, params, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 96)
    kw.setdefault("prefill_buckets", (8, 16, 32))
    kw.setdefault("temperature", 0.0)
    kw.setdefault("steps_per_sync", 4)
    kw.setdefault("kv_block", 8)
    kw.setdefault("kv_pool_blocks", 64)
    kw.setdefault("prefill_chunk", C)
    return ContinuousBatcher(cfg, params, **kw)


def _want(cfg, params, p, n):
    return np.asarray(generate(cfg, params, jnp.asarray(p[None]), n,
                               temperature=0.0))[0]


def _together(eng, prompts, n):
    """Every prompt in the queue before the engine thread looks."""
    return eng.run_on_engine(lambda: [eng.submit(p, n) for p in prompts])


def _tokens(cfg, seed, *lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


@pytest.fixture(scope="module")
def gqa():
    return GQA, _params(GQA)


# -- (a) two in the lane answer what each answers alone ------------------------

@pytest.mark.parametrize("stack", list(STACKS))
def test_two_in_the_lane_answer_what_each_answers_alone(stack):
    """41 and 70 tokens at a chunk of 16, a prefix hit (long itself: the
    lane must pass it to the reuse path, not prefill it whole) between
    them in the queue and a cold short prompt behind: the hit drains,
    the second long prompt joins in the first's tick, the short one
    waits for the lane.  Three ticks carry two chunks (16, 16 and the
    first's last 9); the longer goes on alone."""
    cfg = STACKS[stack]
    params = _params(cfg)
    a, b, seen, short, more = _tokens(cfg, 54, 41, 70, 33, 11, 5)
    hit = np.concatenate([seen, more])
    eng = _engine(cfg, params)
    try:
        eng.submit(seen, 4).result(300)       # its chain is in the pool
        eng.run_on_engine(lambda: None)       # past that tick's commit
        st0 = eng.stats()
        futs = _together(eng, [a, hit, b, short], 6)
        outs = [f.result(300) for f in futs]
        st = eng.stats()
    finally:
        eng.stop()
    for p, out in zip([a, hit, b, short], outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 6))
    grew = {k: st[k] - st0[k] for k in (
        "chunk_pair_dispatches", "prefill_chunks", "chunked_admissions",
        "kv_prefix_hits", "stage_queue_wait_chunk_n")}
    assert grew == {"chunk_pair_dispatches": 3, "prefill_chunks": 3 + 5,
                    "chunked_admissions": 2, "kv_prefix_hits": 1,
                    "stage_queue_wait_chunk_n": 2}, grew
    assert st["queue_wait_cause_lane_s"] > st0["queue_wait_cause_lane_s"]


def test_both_in_the_lane_end_in_one_tick(gqa):
    """Remainders of 3 and 9 after two chunks each: two last chunks in
    one tick, two inserts behind one step."""
    cfg, params = gqa
    a, b = _tokens(cfg, 55, 35, 41)
    eng = _engine(cfg, params, kv_block=0)
    try:
        outs = [f.result(120) for f in _together(eng, [a, b], 5)]
        st = eng.stats()
    finally:
        eng.stop()
    for p, out in zip([a, b], outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 5))
    assert (st["chunk_pair_dispatches"], st["prefill_chunks"]) == (3, 6)


def test_two_in_the_lane_on_a_mesh_answer_the_same(gqa):
    cfg, params = gqa
    a, b = _tokens(cfg, 56, 41, 70)
    eng = _engine(cfg, params, mesh=build_mesh(MeshSpec(dp=-1, tp=2)))
    try:
        outs = [f.result(300) for f in _together(eng, [a, b], 6)]
        st = eng.stats()
    finally:
        eng.stop()
    for p, out in zip([a, b], outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 6))
    assert st["chunk_pair_dispatches"] == 3, st


# -- (b) the lane holds two and no more; the next joins one under way ---------

def _held_after_first_advance(eng, monkeypatch):
    """The engine thread stops inside ``_advance_chunks`` after its
    first call until ``release`` is set."""
    started, release = threading.Event(), threading.Event()
    advance = eng._advance_chunks

    def held():
        pres = advance()
        if not started.is_set():
            started.set()
            assert release.wait(60)
        return pres

    monkeypatch.setattr(eng, "_advance_chunks", held)
    return started, release


def test_a_long_prompt_joins_one_under_way(gqa, monkeypatch):
    """The second arrives after the first's first chunk and joins at
    its own offset 0 beside the first's 16."""
    cfg, params = gqa
    a, b = _tokens(cfg, 61, 70, 41)
    eng = _engine(cfg, params, kv_block=0)
    try:
        started, release = _held_after_first_advance(eng, monkeypatch)
        fa = eng.submit(a, 6)
        assert started.wait(120)
        fb = eng.submit(b, 6)
        release.set()
        outs = [fa.result(120), fb.result(120)]
        st = eng.stats()
    finally:
        eng.stop()
    for p, out in zip([a, b], outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 6))
    # a: 4 chunks and its last; b: 2 and its last, all beside a's
    assert (st["chunk_pair_dispatches"], st["prefill_chunks"]) == (3, 8)
    assert st["queue_wait_cause_lane_s"] == 0


def test_a_third_long_prompt_waits_for_the_lane(gqa):
    cfg, params = gqa
    prompts = _tokens(cfg, 57, 70, 60, 50)
    eng = _engine(cfg, params, kv_block=0)
    try:
        outs = [f.result(120) for f in _together(eng, prompts, 4)]
        st = eng.stats()
    finally:
        eng.stop()
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 4))
    # 70 and 60 side by side for the 60's four chunks; the tick after,
    # the 50 joins the 70's last chunk, then runs its other three alone
    assert st["chunk_pair_dispatches"] == 4 + 1, st
    assert st["chunked_admissions"] == 3
    assert st["prefill_chunks"] == 5 + 4 + 4
    # a slot was free all along: the third waited for the LANE, from the
    # tick the two entered to the tick the shorter left
    assert st["queue_wait_cause_lane_s"] > 0
    assert st["queue_wait_cause_slots_s"] == 0
    # the lane was held once, from the first's entry to the third's end:
    # less than the three stays one after another
    assert st["chunk_lane_busy_s"] < st["stage_prefill_chunk_sum_s"]


# -- (c) a one-lane ladder keeps today's lane ---------------------------------

def test_a_one_lane_ladder_holds_one_prompt_at_a_time(gqa):
    cfg, params = gqa
    a, b = _tokens(cfg, 58, 41, 70)
    eng = _engine(cfg, params, kv_block=0)
    try:
        eng.PREFILL_KS = (1,)       # what _require_fit leaves a tight chip
        eng.warm(41, chunk_finals=True)
        outs = [f.result(120) for f in _together(eng, [a, b], 6)]
        st = eng.stats()
        keys = set(eng._prefill_cache)
    finally:
        eng.stop()
    for p, out in zip([a, b], outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 6))
    # the lane's programs are one prompt's, whatever its width
    assert ("chunk", C) in keys and ("chunk", C, 2) not in keys
    assert st["chunk_pair_dispatches"] == 0
    assert st["prefill_chunks"] == (2 + 1) + (4 + 1)
    assert st["queue_wait_cause_lane_s"] > 0      # b waited for a's stay


# -- (d) two in flight when a program, the tick or the engine fails -----------

def test_a_chunk_that_raises_fails_its_own_request_only(gqa, monkeypatch):
    cfg, params = gqa
    a, b = _tokens(cfg, 62, 80, 70)
    eng = _engine(cfg, params, kv_block=0)
    try:
        mid = eng._chunk_mid_fn(C)

        def broken(params, slab, ids, sown):
            if np.array_equal(np.asarray(ids)[0], a[C:2 * C]):
                raise FloatingPointError("no such program")
            return mid(params, slab, ids, sown)

        monkeypatch.setattr(eng, "_chunk_mid_fn", lambda C: broken)
        fa, fb = _together(eng, [a, b], 4)
        with pytest.raises(FloatingPointError):
            fa.result(120)
        np.testing.assert_array_equal(fb.result(120),
                                      _want(cfg, params, b, 4))
        eng.run_on_engine(lambda: None)
        assert eng._failed_requests == 1
        assert eng._chunking == [] and all(s.free for s in eng._slots)
    finally:
        eng.stop()


@pytest.mark.parametrize("how", ["tick_raises", "stop"])
def test_two_in_flight_fail_both_and_free_both_slots(gqa, how, monkeypatch):
    cfg, params = gqa
    a, b, later = _tokens(cfg, 59, 80, 90, 41)
    eng = _engine(cfg, params, kv_block=0)
    try:
        started, release = _held_after_first_advance(eng, monkeypatch)
        futs = _together(eng, [a, b], 4)
        assert started.wait(120)
        assert [st.offset for st in eng._chunking] == [C, C]
        if how == "tick_raises":
            def broken(live, pres):
                raise FloatingPointError("the device said no")
            monkeypatch.setattr(eng, "_dispatch", broken)
            release.set()
        else:
            stopper = threading.Thread(target=eng.stop)
            stopper.start()
            while not eng._stopping:
                threading.Event().wait(0.001)
            release.set()
            stopper.join(60)
            assert not stopper.is_alive()
        for f in futs:
            with pytest.raises(
                    RuntimeError if how == "stop" else FloatingPointError):
                f.result(120)
        assert eng._chunking == []
        assert all(s.free for s in eng._slots)
        if how != "stop":
            monkeypatch.undo()
            eng.run_on_engine(lambda: None)     # past the failing tick
            assert eng._failed_requests == 2
            # the lane and both slots serve the next long prompt
            np.testing.assert_array_equal(
                eng.submit(later, 4).result(120),
                _want(cfg, params, later, 4))
    finally:
        if how != "stop":
            eng.stop()


# -- (e) after warm() two in the lane compile nothing -------------------------

@pytest.mark.parametrize("where", ["one_chip", "tp2"])
def test_after_warm_two_in_the_lane_lower_no_program(gqa, where):
    """What ``serve_compiles_in_window`` counts, over the whole stay of
    two prompts side by side."""
    cfg, params = gqa
    a, b, alone = _tokens(cfg, 60, 41, 70, 45)
    mesh = build_mesh(MeshSpec(dp=-1, tp=2)) if where == "tp2" else None
    eng = _engine(cfg, params, kv_block=0, mesh=mesh)
    lowered: list[str] = []

    def listen(name, _dur, **kw):
        if name == LOWERED and armed:
            lowered.append(str(kw.get("fun_name", "?")))

    armed = False
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        eng.warm(41, chunk_finals=True)
        # one long prompt alone first: the host's own one-op programs
        # (a key split, an id array) are any first request's
        eng.submit(alone, 6).result(120)
        armed = True
        outs = [f.result(120) for f in _together(eng, [a, b], 6)]
        armed = False
        st = eng.stats()
    finally:
        armed = False
        eng.stop()
    assert st["chunk_pair_dispatches"] == 3, st
    assert lowered == [], lowered
    for p, out in zip([a, b], outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 6))


# -- the metric that says how often it engages --------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chunk_lanes_mean():
    path = os.path.join(ROOT, "benchmarks", "layer_metrics",
                        "engine_chunk_lanes_mean.py")
    spec = importlib.util.spec_from_file_location("_chunk_lanes_mean", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("counters,want", [
    ({"prefill_chunks": 140, "chunk_pair_dispatches": 40}, 1.4),
    ({"prefill_chunks": 140, "chunk_pair_dispatches": 70}, 2.0),
    ({"prefill_chunks": 140, "chunk_pair_dispatches": 0}, 1.0),
    ({"prefill_chunks": 0, "chunk_pair_dispatches": 0}, 1.0),
    ({"prefill_chunks": 140}, None),      # the parent: no such counter
])
def test_engine_chunk_lanes_mean_reader(counters, want):
    got = _chunk_lanes_mean()({"counters": counters})
    assert got == want if want is None else got == pytest.approx(want)


def test_engine_chunk_lanes_mean_is_declared_for_the_serve_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = dict(next(m for m in spec["per_layer"]
                      if m["name"] == "engine_chunk_lanes_mean"))
    cells = entry.pop("workloads")
    assert entry == {
        "name": "engine_chunk_lanes_mean", "unit": "lanes",
        "better": "higher", "source": "program_counter",
        "layer": "engine tick", "moves": "serve_tokens_per_s"}
    assert cells == next(m["workloads"] for m in spec["end_to_end"]
                         if m["name"] == "serve_tokens_per_s")
    assert entry["layer"] in {m["layer"] for m in spec["per_layer"]
                              if m["name"] != entry["name"]}
