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
        want = np.asarray(generate(cfg, params, jnp.asarray(p[None]), n,
                                   temperature=0.0))[0]
        np.testing.assert_array_equal(out, want)


def test_queue_deeper_than_slots(small):
    # more requests than slots: every future resolves, slots recycle
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
        want = np.asarray(generate(cfg, params, jnp.asarray(p[None]), n,
                                   temperature=0.0))[0]
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
        want = np.asarray(generate(cfg, params, jnp.asarray(p[None]), 6,
                                   temperature=0.0))[0]
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
        want = np.asarray(generate(cfg, params, jnp.asarray(p[None]), 6,
                                   temperature=0.0))[0]
        np.testing.assert_array_equal(out, want)


def test_eos_truncates(small):
    cfg, params = small
    # eos = whatever greedy emits second -> output must stop there
    p = np.asarray([5, 9, 2], np.int32)
    ref = np.asarray(generate(cfg, params, jnp.asarray(p[None]), 8,
                              temperature=0.0))[0]
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
    want = np.asarray(generate(cfg, params, jnp.asarray(p[None]), 5,
                               temperature=0.0))[0]
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
    want = np.asarray(generate(cfg, params, jnp.asarray(p[None]), 6,
                               temperature=0.0))[0]
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
    want = np.asarray(generate(cfg, params, jnp.asarray(p[None]), 5,
                               temperature=0.0))[0]
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
