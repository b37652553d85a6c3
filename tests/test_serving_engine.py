"""Continuous batching engine (edl_tpu/serving/engine.py).

The load-bearing property is slot independence: a request decoded
while other slots churn must match the same request decoded alone.
Greedy sampling makes that exact, so parity against
models/generate.generate() is the core assertion.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edl_tpu.models import TransformerConfig, TransformerLM
from edl_tpu.models.generate import generate
from edl_tpu.serving import ContinuousBatcher


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
    kw.setdefault("steps_per_sync", 4)
    return ContinuousBatcher(cfg, params, **kw)


_generate = {}


def _want(cfg, params, p, n):
    """``generate()``'s greedy tokens for prompt ``p``, the parity
    reference of this file, under ``jax.jit`` as its docstring asks (one
    program a configuration, prompt length and ``n``: called eagerly it
    compiles its prefill op by op and its scan again every call, 3 s a
    call against 1 s; D29)."""
    fn = _generate.get(cfg)
    if fn is None:
        fn = _generate[cfg] = jax.jit(
            lambda params, prompt, n: generate(cfg, params, prompt, n,
                                               temperature=0.0),
            static_argnums=2)
    return np.asarray(fn(params, jnp.asarray(p[None]), n))[0]


def test_greedy_parity_vs_generate(small):
    cfg, params = small
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 97, (n,)).astype(np.int32)
               for n in (3, 7, 12, 5, 9, 16, 2)]
    news = [6, 3, 9, 12, 1, 5, 8]
    eng = _engine(cfg, params)
    try:
        futs = [eng.submit(p, n) for p, n in zip(prompts, news)]
        got = [f.result(timeout=120) for f in futs]
    finally:
        eng.stop()
    for p, n, out in zip(prompts, news, got):
        want = _want(cfg, params, p, n)
        np.testing.assert_array_equal(out, want)


def test_queue_deeper_than_slots(small):
    # more requests than slots: every future resolves, slots recycle.
    # Under the one-tick lookahead a slot recycles one tick after the
    # read that freed it (test_slot_freed_at_tick_n_is_admitted_at_
    # n_plus_1 pins the tick): with 9 requests over 2 slots that is 4
    # refills, each behind a tick that was enqueued unread
    cfg, params = small
    rng = np.random.default_rng(1)
    eng = _engine(cfg, params, slots=2)
    try:
        futs = [eng.submit(rng.integers(1, 97, (4,)).astype(np.int32), 5)
                for _ in range(9)]
        outs = [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    finally:
        eng.stop()
    assert all(len(o) == 5 for o in outs)
    assert stats["requests_done"] == 9
    assert stats["queue_depth"] == 0
    assert stats["lookahead_ticks"] >= 5, stats
    assert 0.0 < stats["slot_utilization"] <= 1.0
    assert stats["moe_prefill_drops"] == 0     # dense config never drops


def test_engine_counts_moe_prefill_drops():
    """Continuous-batching prefill surfaces MoE capacity overflow."""
    import dataclasses

    cfg = TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=64,
                            remat=False, dtype=jnp.float32,
                            moe_experts=4, moe_top_k=2, moe_capacity=0.05)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    prompt = np.random.default_rng(3).integers(1, 64, (16,)).astype(np.int32)
    eng = _engine(cfg, params, slots=1)
    try:
        out = eng.generate(prompt, 3, timeout=120)
        assert len(out) == 3
        starved = eng.stats()["moe_prefill_drops"]
    finally:
        eng.stop()
    assert starved > 0, "starved capacity_factor must report drops"

    ample = dataclasses.replace(cfg, moe_capacity=4.0)
    eng2 = _engine(ample, params, slots=1)
    try:
        eng2.generate(prompt, 3, timeout=120)
        assert eng2.stats()["moe_prefill_drops"] == 0
    finally:
        eng2.stop()


def test_tp_sharded_engine_greedy_parity(small):
    """Continuous batching on a tp=2 mesh: params + KV cache sharded,
    slot logic unchanged, tokens match the unsharded engine exactly —
    the serving path for models bigger than one chip's HBM."""
    from edl_tpu.parallel import MeshSpec, build_mesh

    cfg, params = small
    mesh = build_mesh(MeshSpec(dp=-1, tp=2))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 97, (n,)).astype(np.int32)
               for n in (3, 9, 14, 6)]
    news = [7, 4, 10, 6]
    eng = _engine(cfg, params, mesh=mesh)
    try:
        # spot-check the params actually shard (mlp kernel over tp)
        k = eng._params["layer_0"]["mlp_in"]["kernel"]
        assert k.sharding.is_fully_replicated is False
        futs = [eng.submit(p, n) for p, n in zip(prompts, news)]
        got = [f.result(timeout=120) for f in futs]
    finally:
        eng.stop()
    for p, n, out in zip(prompts, news, got):
        want = _want(cfg, params, p, n)
        np.testing.assert_array_equal(out, want)


def test_tp_sharded_jit_teacher_matches():
    """TeacherServer's model wrapper on a tp mesh: sharded forward
    logits match the replicated forward bit-for-bit shape/value-wise."""
    from edl_tpu.distill.teacher import jit_teacher
    from edl_tpu.models.transformer import LOGICAL_RULES
    from edl_tpu.parallel import MeshSpec, build_mesh

    cfg = TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=32,
                            remat=False, dtype=jnp.float32)
    model = TransformerLM(cfg)
    variables = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
    ids = np.random.default_rng(2).integers(0, 64, (2, 8)).astype(np.int32)

    plain = jit_teacher(model.apply, variables)({"ids": ids})["logits"]
    mesh = build_mesh(MeshSpec(dp=-1, tp=2))
    sharded = jit_teacher(model.apply, variables, mesh=mesh,
                          logical_rules=LOGICAL_RULES)({"ids": ids})["logits"]
    np.testing.assert_allclose(sharded, plain, atol=1e-5)


def test_moe_engine_greedy_parity():
    """MoE greedy parity engine-vs-generate: the padded prefill masks
    pad positions out of routing, so a prompt shorter than its bucket
    matches generate() on the unpadded prompt (ample capacity — see
    compute_routing's valid test for the tight-capacity invariant)."""
    cfg = TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=64,
                            remat=False, dtype=jnp.float32,
                            moe_experts=4, moe_top_k=2, moe_capacity=4.0)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 64, (n,)).astype(np.int32) for n in (3, 7, 13)]
    eng = _engine(cfg, params, slots=2)
    try:
        futs = [eng.submit(p, 6) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
    finally:
        eng.stop()
    for p, out in zip(prompts, got):
        want = _want(cfg, params, p, 6)
        np.testing.assert_array_equal(out, want)


def test_gqa_engine_greedy_parity(small):
    """Continuous batching over a GQA model: grouped decode cache per
    slot still matches isolated generate() exactly."""
    import dataclasses

    cfg = dataclasses.replace(small[0], num_kv_heads=2)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 97, (n,)).astype(np.int32)
               for n in (3, 8, 12, 5)]
    eng = _engine(cfg, params, slots=2)
    try:
        futs = [eng.submit(p, 6) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
    finally:
        eng.stop()
    for p, out in zip(prompts, got):
        want = _want(cfg, params, p, 6)
        np.testing.assert_array_equal(out, want)


def test_eos_truncates(small):
    cfg, params = small
    # eos = whatever greedy emits second -> output must stop there
    p = np.asarray([5, 9, 2], np.int32)
    ref = _want(cfg, params, p, 8)
    eos = int(ref[1])
    eng = _engine(cfg, params, eos_id=eos)
    try:
        out = eng.generate(p, 8, timeout=120)
    finally:
        eng.stop()
    assert list(out) == list(ref[:2])


def test_submit_validation(small):
    cfg, params = small
    eng = _engine(cfg, params)
    try:
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.zeros((0,), np.int32), 4)
        with pytest.raises(ValueError, match="room"):
            eng.submit(np.zeros((64,), np.int32), 1)   # no room to generate
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(np.zeros((16,), np.int32), 60)  # 16 + 60 > 64
    finally:
        eng.stop()


def test_prompt_longer_than_configured_buckets(small):
    """The prompt cap is the CACHE, not the bucket list: buckets extend
    by doubling to cache_len, so a 17-token prompt serves fine with
    configured buckets (8, 16) and a 64 cache — greedy parity holds."""
    cfg, params = small
    p = np.random.default_rng(9).integers(1, 97, (17,)).astype(np.int32)
    eng = _engine(cfg, params)
    try:
        assert eng.stats()["max_prompt_len"] == 63
        out = eng.generate(p, 5, timeout=120)
    finally:
        eng.stop()
    want = _want(cfg, params, p, 5)
    np.testing.assert_array_equal(out, want)


@pytest.mark.slow
def test_600_token_prompt_1024_cache():
    """VERDICT r4 #4's acceptance case: a 1024-cache engine must accept
    a 600-token prompt with the DEFAULT bucket list (max 512)."""
    cfg = TransformerConfig(vocab_size=61, num_layers=1, embed_dim=16,
                            num_heads=2, mlp_dim=32, max_len=1024,
                            remat=False, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    p = np.random.default_rng(4).integers(1, 61, (600,)).astype(np.int32)
    eng = ContinuousBatcher(cfg, params, slots=2, temperature=0.0,
                            steps_per_sync=4)
    try:
        out = eng.generate(p, 6, timeout=300)
    finally:
        eng.stop()
    want = _want(cfg, params, p, 6)
    np.testing.assert_array_equal(out, want)


def test_mixed_load_decode_not_starved(small):
    """Decode lanes advance no matter how fast new requests arrive: two
    long generations run to completion while a queue of short arrivals
    churns through the remaining slot.  Starvation is gated on the
    engine's OWN scheduler accounting — the longs' completion proves
    liveness, ``requests_done`` proves the churn was real, and the
    wall-clock ratio is a wide LOAD-TOLERANT backstop only (ISSUE 13
    deflake: the old 2x bound tripped under the full tier-1 suite on a
    1-core box purely from host scheduler jitter; a device-class
    ratio is a chip measurement, not this test's)."""
    import time as _t

    cfg, params = small
    LONG, SHORT = 40, 4

    def run(churn: int) -> float:
        eng = _engine(cfg, params, slots=3, steps_per_sync=4)
        try:
            t0 = _t.monotonic()
            longs = [eng.submit(np.asarray([7, 11, 13], np.int32), LONG)
                     for _ in range(2)]
            shorts = [eng.submit(np.asarray([5, 9], np.int32), SHORT)
                      for _ in range(churn)]
            for f in longs:
                f.result(timeout=300)
            dt = _t.monotonic() - t0
            for f in shorts:
                f.result(timeout=300)
            stats = eng.stats()
        finally:
            eng.stop()
        if churn:
            # shorts prefill while the longs decode: stall is accounted
            # (no assertion on the quiet run — whether its two submits
            # land in one idle-engine prefill group is a thread race)
            assert stats["prefill_stall_s"] > 0.0
            assert stats["requests_done"] == 2 + churn
        return dt

    quiet = run(churn=0)
    busy = run(churn=12)
    # backstop, not the starvation oracle: a starved decode lane would
    # take ~churn/slots times longer (the longs would queue behind every
    # short), so 4x + a flat 8s scheduler allowance cleanly separates
    # "starved" from "loaded CI host" without flaking under tier-1
    assert busy <= max(4.0 * quiet, quiet + 8.0), (
        f"long decodes starved by arrivals: quiet {quiet:.2f}s vs "
        f"busy {busy:.2f}s")


def test_warm_then_serve(small):
    """warm() pre-compiles the step + every prefill/insert sub-batch
    without touching live state: the engine must serve identically
    afterwards (greedy parity), and the ladder must scale with slots."""
    cfg, params = small
    eng = _engine(cfg, params, slots=3)
    try:
        assert eng.PREFILL_KS == (2, 1)   # ladder filtered by slots
        eng.warm(7)
        p = np.random.default_rng(21).integers(1, 97, (7,)).astype(np.int32)
        out = eng.generate(p, 5, timeout=120)
    finally:
        eng.stop()
    want = _want(cfg, params, p, 5)
    np.testing.assert_array_equal(out, want)


def test_warm_mid_traffic_fails_loudly(small):
    """warm() shares the donated pool cache with the engine thread, so
    calling it with requests in flight must raise, not race (ISSUE 2
    satellite): occupied slots or queued work both refuse."""
    cfg, params = small
    eng = _engine(cfg, params, slots=2)
    try:
        fut = eng.submit(np.asarray([3, 1, 4], np.int32), 8)
        # wait until the request occupies a slot (not the queue->pending
        # handoff instant) so the guard trips on a deterministic state
        deadline = time.monotonic() + 120
        while not eng.stats()["active_slots"]:
            assert time.monotonic() < deadline, "request never admitted"
            time.sleep(0.01)
        with pytest.raises(RuntimeError, match="in flight"):
            eng.warm(7)
        fut.result(timeout=120)   # the live request still completes
        # drained again: warm() is legal once traffic is gone
        eng.warm(7)
    finally:
        eng.stop()


def test_stop_fails_pending(small):
    cfg, params = small
    eng = _engine(cfg, params, slots=1)
    futs = [eng.submit(np.asarray([3, 4], np.int32), 30) for _ in range(4)]
    eng.stop()
    # all futures resolve one way or the other — none hang
    done = sum(1 for f in futs if f.done())
    assert done == 4


def test_drain_completes_queued_and_inflight(small):
    """drain() is the graceful replica-removal path: admission stops,
    but every queued + in-flight request runs to completion — where
    stop() (the hard path above) FAILS them."""
    import threading

    cfg, params = small
    eng = _engine(cfg, params, slots=1)   # 1 slot: most requests queued
    futs = [eng.submit(np.asarray([3, 4], np.int32), 8) for _ in range(5)]
    drained = []
    t = threading.Thread(target=lambda: drained.append(eng.drain()))
    t.start()
    # the draining flag is up before completion: new submits refuse
    deadline = time.monotonic() + 120
    while not eng.stats()["draining"]:
        assert time.monotonic() < deadline, "drain flag never observed"
        time.sleep(0.005)
    with pytest.raises(RuntimeError, match="draining|stopping"):
        eng.submit(np.asarray([5], np.int32), 4)
    t.join(timeout=120)
    assert drained == [True]
    for f in futs:
        out = f.result(timeout=1)         # resolved, with real tokens
        assert len(out) == 8


def test_drain_timeout_falls_back_to_hard_stop(small):
    cfg, params = small
    eng = _engine(cfg, params, slots=1)
    futs = [eng.submit(np.asarray([3, 4], np.int32), 40) for _ in range(3)]
    assert eng.drain(timeout=0.0) is False   # deadline already passed
    # hard-stop fallback: every future resolves (with an error), none hang
    for f in futs:
        assert f.done()
    assert sum(1 for f in futs if f.exception() is not None) >= 1


# -- the one-tick lookahead (ISSUE 31) ---------------------------------------
# The engine enqueues tick n+1 before it reads tick n.  Everything above
# already runs through it; these cases pin what it rests on: budgets the
# host can count, an EOS read one tick late, a flush wherever host and
# device have to agree.

def _first_time(ref, k0):
    """The first index >= k0 whose token did not occur before it in
    ``ref``: an ``eos_id`` that ends the stream exactly there."""
    return next(k for k in range(k0, len(ref))
                if int(ref[k]) not in set(map(int, ref[:k])))


class _Hold:
    """Parks the engine thread at its next read that has a newer tick
    enqueued behind it: two ticks in flight, for as long as the test
    wants.  Steps through further such reads on ``release()``."""

    def __init__(self, eng):
        import threading
        self.held, self._go = threading.Event(), threading.Event()
        self._armed, real = True, eng._read

        def read(tick):
            if (self._armed and tick is not None
                    and eng._inflight is not None):
                self._armed = False
                self.held.set()
                assert self._go.wait(120)
            real(tick)

        eng._read = read

    def release(self):
        self._go.set()


def _count_steps(eng):
    """Calls of the decode step program from here on."""
    calls, real = [], eng._step_jit

    def step(*args):
        calls.append(1)
        return real(*args)

    eng._step_jit = step
    return calls


_LOOKAHEAD_CFGS = {
    "dense": {},
    "gqa": {"num_kv_heads": 2},
    "moe-dropless": {"moe_experts": 4, "moe_top_k": 2, "moe_capacity": 0.0,
                     "moe_gated": True},
    "window": {"num_layers": 4, "max_len": 96, "attn_window": 8,
               "layer_attn": ("window", "global", "global", "window")},
}


@pytest.mark.parametrize("kind", sorted(_LOOKAHEAD_CFGS))
def test_lookahead_greedy_parity(small, kind):
    """Slots that churn while every tick is enqueued behind an unread
    one: tokens equal ``generate()``'s, the lookahead engaged, nothing
    was discarded (no ``eos_id``: the budgets are exact)."""
    import dataclasses

    cfg = dataclasses.replace(small[0], **_LOOKAHEAD_CFGS[kind])
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, 97, (n,)).astype(np.int32)
               for n in (3, 7, 12, 5, 9, 16, 2)]
    news = [6, 4, 9, 13, 1, 5, 8]
    eng = _engine(cfg, params, slots=2, kv_block=4, kv_pool_blocks=64)
    try:
        futs = [eng.submit(p, n) for p, n in zip(prompts, news)]
        got = [f.result(timeout=120) for f in futs]
        stats = eng.stats()
    finally:
        eng.stop()
    for p, n, out in zip(prompts, news, got):
        np.testing.assert_array_equal(out, _want(cfg, params, p, n))
    assert stats["lookahead_ticks"] > 0.5 * stats["ticks"], stats
    assert stats["lookahead_discarded_token_steps"] == 0
    if kind == "moe-dropless":      # the recount the benchmark makes
        assert stats["moe_assignments"] == (
            cfg.num_layers * cfg.moe_top_k * stats["moe_tokens"])


@pytest.mark.parametrize("max_new", [1, 2, 4, 5, 8, 9])
def test_budget_ends_without_an_overrun_program(small, max_new):
    """The host counts programs, not tokens it has read: a budget that
    ends inside tick n takes its slot out of tick n+1's mask, so a
    request costs ceil((max_new - 1) / T) step programs and not one
    more (``max_new`` 1: the prefill's token alone, no step at all)."""
    cfg, params = small
    p = np.asarray([5, 9, 2, 7], np.int32)
    eng = _engine(cfg, params, slots=1, steps_per_sync=4)
    try:
        calls = _count_steps(eng)
        out = eng.generate(p, max_new, timeout=120)
        eng.run_on_engine(lambda: None)       # whatever was enqueued, read
        stats = eng.stats()
    finally:
        eng.stop()
    np.testing.assert_array_equal(out, _want(cfg, params, p, max_new))
    assert len(calls) == -(-(max_new - 1) // 4)
    assert stats["lookahead_discarded_token_steps"] == 0


@pytest.mark.parametrize("kind", ["dense", "window"])
def test_eos_in_the_middle_of_a_program(small, kind):
    """An EOS is data: the host reads it one tick late, the slot has by
    then run one more program as live, and exactly those token steps
    are discarded.  The stream, the slot's commit to the pool and the
    next request admitted into the slot are those of an engine that
    never looked ahead (``generate()`` cut at the EOS; a cold engine).
    The window rings keep ``steps_per_sync`` more positions for it."""
    import dataclasses

    cfg = dataclasses.replace(small[0], **_LOOKAHEAD_CFGS[kind])
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    T = 4
    p = np.random.default_rng(5).integers(1, 97, (13,)).astype(np.int32)
    ref = _want(cfg, params, p, 16)
    # ref[0] is the prefill's; program 1 emits ref[1:5], program 2
    # ref[5:9]: an EOS at index 5-7 is in the middle of program 2
    k = _first_time(ref, 5)
    assert k <= 7, (k, ref)
    eos = int(ref[k])
    kw = dict(slots=1, steps_per_sync=T, eos_id=eos, kv_block=4,
              kv_pool_blocks=64)
    eng = _engine(cfg, params, **kw)
    cold = _engine(cfg, params, **dict(kw, kv_block=0))
    try:
        if kind == "window":
            ring = eng._cache["layer_0"]["cached_key"].shape[-1]
            assert ring == 8 + 4 + (T - 1) + T
        calls = _count_steps(eng)
        out = eng.generate(p, 16, timeout=120)
        eng.run_on_engine(lambda: None)
        assert list(out) == list(ref[:k + 1])
        # program 3 was enqueued when program 2 was read: discarded
        assert len(calls) == 3
        st = eng.stats()
        assert st["lookahead_discarded_token_steps"] == T
        # the slot again, by a stranger: a fresh engine's tokens
        q = np.asarray([3, 1, 4, 1, 5, 9], np.int32)
        np.testing.assert_array_equal(eng.generate(q, 7, timeout=120),
                                      cold.generate(q, 7, timeout=120))
        # and what the slot committed: prompt + emitted[:-1], whole
        # blocks, resumed by the conversation's next turn
        p2 = np.concatenate([p, out, np.asarray([8, 6], np.int32)])
        np.testing.assert_array_equal(eng.generate(p2, 6, timeout=120),
                                      cold.generate(p2, 6, timeout=120))
        st = eng.stats()
        assert st["kv_prefix_hits"] == 1, st
        assert st["kv_prefill_tokens_skipped"] == (13 + k) // 4 * 4
    finally:
        eng.stop()
        cold.stop()


def test_first_token_eos_is_read_one_tick_late(small):
    """The prefill's own token is the EOS: the slot was live in the one
    step enqueued before that was read."""
    cfg, params = small
    p = np.asarray([5, 9, 2], np.int32)
    ref = _want(cfg, params, p, 4)
    eng = _engine(cfg, params, slots=1, eos_id=int(ref[0]))
    try:
        out = eng.generate(p, 9, timeout=120)
        eng.run_on_engine(lambda: None)
        st = eng.stats()
    finally:
        eng.stop()
    assert list(out) == [int(ref[0])]
    assert st["lookahead_discarded_token_steps"] == 4


def test_slot_freed_at_tick_n_is_admitted_at_n_plus_1(small):
    """A queue deeper than the slots: a slot READ as finished in tick n
    (after tick n+1's programs were enqueued) takes its next request in
    tick n+1's ``_admit``, never in the tick that freed it and never
    later while requests wait."""
    cfg, params = small
    rng = np.random.default_rng(2)
    eng = _engine(cfg, params, slots=2)
    events = []
    tick_no = lambda: eng._ledger.totals()["steps"]  # noqa: E731
    finish, stamp = eng._finish, eng._stamp_admit

    def spy_finish(slot):
        events.append(("finish", tick_no(), len(eng._pending)))
        finish(slot)

    def spy_stamp(reqs, lane):
        events.append(("admit", tick_no(), len(reqs)))
        stamp(reqs, lane)

    eng._finish, eng._stamp_admit = spy_finish, spy_stamp
    try:
        hold = _Hold(eng)
        futs = [eng.submit(rng.integers(1, 97, (4,)).astype(np.int32), 6)
                for _ in range(7)]
        assert hold.held.wait(120)
        hold.release()
        outs = [f.result(timeout=120) for f in futs]
    finally:
        eng.stop()
    assert all(len(o) == 6 for o in outs)
    admits = [(t, n) for kind, t, n in events if kind == "admit"]
    frees = {t for kind, t, waiting in events
             if kind == "finish" and waiting}
    assert len(admits) >= 4
    # the first fill is one admission of two requests, or two of one
    # where the engine's first tick beat the second submit() (a loaded
    # host: seen once in 970 tests on 6 workers, PR 32)
    filled = 1 if admits[0][1] == 2 else 2
    # every admission after the first fill follows a read that freed a
    # slot, by exactly one tick; and every such read is followed
    assert {t - 1 for t, _ in admits[filled:]} == frees, events


def test_tasks_see_flushed_state_with_two_ticks_in_flight(small):
    """``run_on_engine`` while one tick is unread and the next is
    enqueued: the closure runs after both were read and booked (what
    the host has not read equals what it has not enqueued)."""
    cfg, params = small
    eng = _engine(cfg, params, slots=2)
    try:
        hold = _Hold(eng)
        futs = [eng.submit(np.asarray([3, 1, 4], np.int32), 30),
                eng.submit(np.asarray([2, 7], np.int32), 30)]
        assert hold.held.wait(120)
        assert eng._inflight is not None          # and one being read

        def look():
            busy = [s for s in eng._slots if not s.free]
            return (eng._inflight, len(busy),
                    [(s.remaining, s.owed, len(s.emitted)) for s in busy])

        import threading
        seen = []
        th = threading.Thread(
            target=lambda: seen.append(eng.run_on_engine(look)))
        th.start()
        time.sleep(0.05)
        assert not seen                           # parked behind the read
        hold.release()
        th.join(120)
        inflight, n_busy, slots = seen[0]
        assert inflight is None and n_busy == 2
        for remaining, owed, n_emitted in slots:
            assert remaining == owed and n_emitted == 30 - remaining
        for f in futs:
            assert len(f.result(timeout=120)) == 30
    finally:
        eng.stop()


def test_session_migration_with_two_ticks_in_flight(small):
    """``drain()`` + ``export_sessions()`` on an engine caught with two
    ticks in flight, ``import_session()`` into another one caught the
    same way: the chain is whole, and the session's next turn there
    resumes from it with a cold engine's tokens."""
    import threading

    cfg, params = small
    kw = dict(slots=2, kv_block=4, kv_pool_blocks=64)
    p1 = np.asarray([7, 11, 13, 5, 9, 2, 8], np.int32)
    eng_a = _engine(cfg, params, **kw)
    try:
        hold = _Hold(eng_a)
        fut = eng_a.submit(p1, 10, session="s")
        assert hold.held.wait(120)
        drained = []
        th = threading.Thread(target=lambda: drained.append(
            eng_a.drain(timeout=60)))
        th.start()
        hold.release()
        th.join(120)
        assert drained == [True]
        out1 = fut.result(timeout=1)
        np.testing.assert_array_equal(out1, _want(cfg, params, p1, 10))
        (name, tokens, meta, blob), = eng_a.export_sessions()
    finally:
        eng_a.stop()
    conv = np.concatenate([p1, out1])
    assert name == "s" and tokens == list(map(int, conv[:16]))

    eng_b = _engine(cfg, params, **kw)
    try:
        hold = _Hold(eng_b)
        other = eng_b.submit(np.asarray([3, 1, 4], np.int32), 24)
        assert hold.held.wait(120)
        got = []
        th = threading.Thread(target=lambda: got.append(
            eng_b.import_session("s", tokens, meta, blob)))
        th.start()
        hold.release()
        th.join(120)
        assert got and got[0] > 0
        p2 = np.concatenate([conv, np.asarray([4, 1], np.int32)])
        out2 = eng_b.submit(p2, 6, session="s").result(timeout=120)
        np.testing.assert_array_equal(out2, _want(cfg, params, p2, 6))
        assert len(other.result(timeout=120)) == 24
        stats = eng_b.stats()
    finally:
        eng_b.stop()
    assert stats["kv_prefix_hits"] == 1, stats
    assert stats["kv_prefill_tokens_skipped"] == 16


@pytest.mark.parametrize("how", ["drain", "stop"])
def test_shutdown_with_two_ticks_in_flight_resolves_every_future(small, how):
    import threading

    cfg, params = small
    eng = _engine(cfg, params, slots=2)
    hold = _Hold(eng)
    futs = [eng.submit(np.asarray([3, 4, n], np.int32), 12)
            for n in range(1, 6)]
    assert hold.held.wait(120)
    th = threading.Thread(target=eng.drain if how == "drain" else eng.stop)
    th.start()
    time.sleep(0.05)
    hold.release()
    th.join(120)
    assert not th.is_alive()
    assert all(f.done() for f in futs)
    if how == "drain":                     # graceful: whole answers
        assert all(len(f.result()) == 12 for f in futs)
    eng.stop()


def test_failing_program_fails_both_ticks_and_the_engine_serves_on(small):
    """A device error surfaces when its program's results are READ, a
    tick after it was enqueued: by then the next tick's programs and
    admissions are enqueued too.  Every request of either tick fails
    (none hangs), and the engine serves the next one."""
    cfg, params = small
    eng = _engine(cfg, params, slots=3)

    class Poisoned:
        def __array__(self, *a, **k):
            raise RuntimeError("injected device error")

    armed, real = [], eng._step_jit

    def step(*args):
        cache, toks, dec, sown = real(*args)
        if armed:
            armed.clear()
            return cache, toks, Poisoned(), sown
        return cache, toks, dec, sown

    eng._step_jit = step
    try:
        hold = _Hold(eng)
        a = eng.submit(np.asarray([3, 1, 4], np.int32), 40)
        assert hold.held.wait(120)
        # B is admitted in the tick whose step is poisoned, C (another
        # bucket: the next cold group) in the tick after it, which is
        # enqueued before the poisoned one is read
        b = eng.submit(np.asarray([2, 7, 1], np.int32), 40)
        c = eng.submit(np.arange(1, 13, dtype=np.int32), 40)
        armed.append(1)
        hold.release()
        for f in (a, b, c):
            with pytest.raises(RuntimeError, match="injected"):
                f.result(timeout=120)
        p = np.asarray([5, 9, 2, 6], np.int32)
        out = eng.generate(p, 7, timeout=120)
        assert eng.drain(timeout=60)
    finally:
        eng.stop()
    np.testing.assert_array_equal(out, _want(cfg, params, p, 7))
