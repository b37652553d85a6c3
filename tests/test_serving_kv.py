"""Paged KV cache + prefix reuse (edl_tpu/serving/kv_cache.py, engine
integration).

The load-bearing property is the same one the engine already proves for
slot independence, extended to chain reuse: a request admitted FROM a
cached prefix must emit bit-identical tokens to the same request
prefilled from scratch (greedy sampling makes that exact).  Everything
else — commit, eviction, session pinning, export/import — must never
bend that invariant.
"""

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
    kw.setdefault("kv_block", 4)
    kw.setdefault("kv_pool_blocks", 64)
    return ContinuousBatcher(cfg, params, **kw)


_generate = {}


def _want(cfg, params, p, n):
    """``generate()``'s greedy tokens under ``jax.jit`` (one program a
    configuration, prompt length and ``n``; eager it compiles its
    prefill op by op and its scan again every call: D29, as
    ``tests/test_serving_engine.py`` does)."""
    fn = _generate.get(cfg)
    if fn is None:
        fn = _generate[cfg] = jax.jit(
            lambda params, prompt, n: generate(cfg, params, prompt, n,
                                               temperature=0.0),
            static_argnums=2)
    return np.asarray(fn(params, jnp.asarray(p[None]), n))[0]


def test_paged_engine_greedy_parity_and_prefix_hits(small):
    """Shared-prefix traffic: the first request commits the chain, the
    rest resume from it — every output bit-identical to generate(), and
    the stats prove the reuse actually happened."""
    cfg, params = small
    rng = np.random.default_rng(0)
    prefix = rng.integers(1, 97, (12,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(1, 97, (n,)).astype(np.int32)])
               for n in (3, 7, 2, 5)]
    eng = _engine(cfg, params)
    try:
        # serialized: each request commits before the next matches (a
        # burst would cold-prefill concurrently — still correct, but
        # this test is about the hit path)
        outs = [eng.generate(p, 6, timeout=120) for p in prompts]
        stats = eng.stats()
    finally:
        eng.stop()
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 6))
    assert stats["kv_prefix_hits"] >= len(prompts) - 1, stats
    assert stats["kv_prefill_tokens_skipped"] >= (len(prompts) - 1) * 12, \
        stats
    assert stats["kv_blocks_used"] > 0


def test_paged_matches_unpaged_engine_bit_exact(small):
    """The acceptance gate: the SAME workload through a paged and an
    unpaged engine yields byte-identical outputs (mixed hits, misses,
    bursts)."""
    cfg, params = small
    rng = np.random.default_rng(1)
    prefix = rng.integers(1, 97, (9,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(1, 97, (n,)).astype(np.int32)])
               for n in (2, 6, 3)]
    prompts += [rng.integers(1, 97, (5,)).astype(np.int32)]  # unrelated
    news = [5, 7, 4, 6]

    def run(**kw):
        eng = _engine(cfg, params, **kw)
        try:
            return [eng.generate(p, n, timeout=120)
                    for p, n in zip(prompts, news)]
        finally:
            eng.stop()

    paged = run()
    unpaged = run(kv_block=0)
    for a, b in zip(paged, unpaged):
        np.testing.assert_array_equal(a, b)


def test_cow_divergence_never_corrupts_sibling_chain(small):
    """Two sessions share a prefix chain, then diverge: committed
    blocks are immutable (divergence writes NEW blocks under new chain
    keys), so each sibling's continuation stays bit-identical to a
    fresh-cache run no matter how the other mutates its own line."""
    cfg, params = small
    rng = np.random.default_rng(2)
    shared = rng.integers(1, 97, (10,)).astype(np.int32)
    eng = _engine(cfg, params)
    try:
        p_a = np.concatenate([shared, np.asarray([3, 1, 4], np.int32)])
        p_b = np.concatenate([shared, np.asarray([2, 7], np.int32)])
        out_a = eng.submit(p_a, 8, session="a").result(120)
        out_b = eng.submit(p_b, 8, session="b").result(120)
        # second turns, interleaved: each extends ITS OWN divergent line
        p_a2 = np.concatenate([p_a, out_a, np.asarray([5], np.int32)])
        p_b2 = np.concatenate([p_b, out_b, np.asarray([9, 6], np.int32)])
        out_a2 = eng.submit(p_a2, 6, session="a").result(120)
        out_b2 = eng.submit(p_b2, 6, session="b").result(120)
        stats = eng.stats()
    finally:
        eng.stop()
    for p, n, out in ((p_a, 8, out_a), (p_b, 8, out_b),
                      (p_a2, 6, out_a2), (p_b2, 6, out_b2)):
        np.testing.assert_array_equal(out, _want(cfg, params, p, n))
    assert stats["kv_sessions"] == 2
    assert stats["kv_prefix_hits"] >= 2   # both second turns resumed


def test_near_max_len_reuse_shortens_chain_not_cache(small):
    """A prompt near max_len whose matched chain + bucketed suffix
    would overhang the cache must shorten the chain (the cache write is
    a CLAMPED dynamic_update_slice — an overhanging slab would silently
    shift backwards over the gathered prefix and poison the pool at
    commit).  Both the overhanging request and a later sibling reusing
    the same chain stay bit-exact."""
    cfg, params = small          # max_len=64, kv_block=4 via _engine
    rng = np.random.default_rng(4)
    p_a = rng.integers(1, 97, (60,)).astype(np.int32)
    # shares 52 tokens (13 blocks) with p_a; suffix of 9 buckets to 16,
    # so 52 + 16 > 64 forces the guard to pop down to a 48-token prefix
    p_b = np.concatenate([p_a[:52],
                          rng.integers(1, 97, (9,)).astype(np.int32)])
    # fits exactly (56 + bucket(2)=8 == 64): proves p_b's admission did
    # not corrupt the committed chain it partially reused
    p_c = np.concatenate([p_a[:56],
                          rng.integers(1, 97, (2,)).astype(np.int32)])
    eng = _engine(cfg, params)
    try:
        out_a = eng.generate(p_a, 4, timeout=120)
        out_b = eng.generate(p_b, 3, timeout=120)
        out_c = eng.generate(p_c, 3, timeout=120)
        stats = eng.stats()
    finally:
        eng.stop()
    np.testing.assert_array_equal(out_a, _want(cfg, params, p_a, 4))
    np.testing.assert_array_equal(out_b, _want(cfg, params, p_b, 3))
    np.testing.assert_array_equal(out_c, _want(cfg, params, p_c, 3))
    assert stats["kv_prefix_hits"] >= 2, stats


def test_eviction_under_pressure_keeps_parity(small):
    """A pool far too small for the traffic must evict (or skip
    commits) — never corrupt: every output still greedy-exact."""
    cfg, params = small
    rng = np.random.default_rng(3)
    eng = _engine(cfg, params, slots=2, kv_pool_blocks=9)
    try:
        for _ in range(10):
            p = rng.integers(1, 97,
                             (int(rng.integers(6, 14)),)).astype(np.int32)
            out = eng.generate(p, 5, timeout=120)
            np.testing.assert_array_equal(out, _want(cfg, params, p, 5))
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["kv_evictions"] > 0 or stats["kv_commit_skips"] > 0, stats
    assert stats["kv_blocks_free"] >= 0


def test_export_import_roundtrip_resumes_warm(small):
    """The migration primitive: a pinned chain exported after drain()
    imports into a second engine, and the session's next turn there
    skips the prefix prefill — bit-identical output."""
    cfg, params = small
    p1 = np.asarray([7, 11, 13, 5, 9, 2, 8], np.int32)
    eng_a = _engine(cfg, params)
    conv = None
    try:
        out1 = eng_a.submit(p1, 8, session="s").result(120)
        np.testing.assert_array_equal(out1, _want(cfg, params, p1, 8))
        conv = np.concatenate([p1, out1])
        assert eng_a.drain(timeout=30)
        exported = eng_a.export_sessions()
        assert [e[0] for e in exported] == ["s"]
        _, tokens, meta, blob = exported[0]
        # the chain covers full blocks of prompt + emitted[:-1]
        assert tokens == list(map(int, conv[:len(tokens)]))
    finally:
        eng_a.stop()

    eng_b = _engine(cfg, params)
    try:
        assert eng_b.import_session("s", tokens, meta, blob) > 0
        assert eng_b.stats()["kv_sessions"] == 1
        p2 = np.concatenate([conv, np.asarray([4, 1], np.int32)])
        out2 = eng_b.generate(p2, 6, timeout=120)
        np.testing.assert_array_equal(out2, _want(cfg, params, p2, 6))
        stats = eng_b.stats()
        assert stats["kv_prefix_hits"] == 1, stats
        assert stats["kv_prefill_tokens_skipped"] == len(tokens), stats
    finally:
        eng_b.stop()


def test_match_pays_for_one_key_at_a_miss(small):
    """The engine re-matches the queue's front request on every tick it
    cannot admit it, so a walk of the trie builds its keys one at a
    time: a cold prompt costs one block's key whatever its length, a
    hit costs its chain plus the block that ends it."""
    cfg, params = small

    class Counting:
        def __init__(self, ids):
            self.ids, self.slices = ids, 0

        def __len__(self):
            return len(self.ids)

        def __getitem__(self, k):
            self.slices += 1
            return self.ids[k]

    rng = np.random.default_rng(3)
    warm = rng.integers(1, 97, (21,)).astype(np.int32)
    eng = _engine(cfg, params, slots=2)
    try:
        eng.generate(warm, 3, timeout=120)          # commits 5 blocks of 4
        cold = Counting(rng.integers(1, 97, (60,)).astype(np.int32))
        assert eng._kv.match(cold) == [] and cold.slices == 1
        hit = Counting(np.concatenate([warm[:12], cold.ids[:30]]))
        assert len(eng._kv.match(hit)) == 3 and hit.slices == 4
    finally:
        eng.stop()


def test_import_refused_without_paging(small):
    cfg, params = small
    eng = _engine(cfg, params, kv_block=0)
    try:
        with pytest.raises(RuntimeError, match="disabled"):
            eng.import_session("s", [1, 2, 3, 4], {"block": 4, "n": 1,
                                                   "layers": [],
                                                   "layout": {}}, b"")
    finally:
        eng.stop()


def test_reuse_off_still_commits_for_migration(small):
    """prefix_reuse=False: admissions always cold-prefill (misses only)
    but chains still commit + pin, so drain migration keeps working."""
    cfg, params = small
    eng = _engine(cfg, params, prefix_reuse=False)
    try:
        p = np.asarray([5, 9, 2, 7, 1], np.int32)
        eng.submit(p, 6, session="s").result(120)
        p2 = np.concatenate([p, np.asarray([3], np.int32)])
        out = eng.generate(p2, 4, timeout=120)
        np.testing.assert_array_equal(out, _want(cfg, params, p2, 4))
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["kv_prefix_hits"] == 0
    assert stats["kv_sessions"] == 1
    assert stats["kv_blocks_used"] > 0


def test_mesh_engine_accepts_paging_bit_exact(small):
    """ISSUE 20 flipped the old refusal: a tp>1 engine now pages by
    sharding the pool over the head axis (one shared host trie, every
    pool op lifted through shard_map) — shared-prefix traffic on a mesh
    engine must hit the trie AND stay bit-identical to generate()."""
    from edl_tpu.parallel import MeshSpec, build_mesh

    cfg, params = small
    mesh = build_mesh(MeshSpec(dp=-1, tp=2))
    rng = np.random.default_rng(7)
    prefix = rng.integers(1, 97, (12,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(1, 97, (n,)).astype(np.int32)])
               for n in (3, 6, 2)]
    eng = _engine(cfg, params, slots=2, mesh=mesh)
    try:
        outs = [eng.generate(p, 5, timeout=120) for p in prompts]
        stats = eng.stats()
    finally:
        eng.stop()
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 5))
    assert stats["kv_prefix_hits"] >= len(prompts) - 1, stats


def test_engine_that_cannot_fit_is_refused_at_construction(small,
                                                           monkeypatch):
    """A device that reports a memory limit gets the arithmetic done
    before anything is allocated: an engine whose slabs + self-sized
    pool + widest prefill exceed it raises with every size named,
    instead of an XLA allocation error on some later request."""
    cfg, params = small

    class _Chip:
        device_kind = "toy chip"

        def __init__(self, limit):
            self._limit = limit

        def memory_stats(self):
            return {"bytes_limit": self._limit, "bytes_in_use": 1 << 20}

    monkeypatch.setattr(jax, "devices", lambda: [_Chip((1 << 20) + 4096)])
    with pytest.raises(ValueError) as err:
        ContinuousBatcher(cfg, params, slots=2, kv_block=8)
    for part in ("toy chip", "2 slots x", "slot slabs", "block pool",
                 "blocks of 8", "widest prefill", "GiB limit"):
        assert part in str(err.value), err.value
    monkeypatch.setattr(jax, "devices", lambda: [_Chip(1 << 40)])
    eng = ContinuousBatcher(cfg, params, slots=2, kv_block=8)
    eng.stop()


# -- window layers: the second allocation class (PR 30) ----------------------

@pytest.fixture(scope="module")
def hybrid():
    """Two window layers (window 8) around two global ones."""
    cfg = TransformerConfig(vocab_size=97, num_layers=4, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=96,
                            remat=False, dtype=jnp.float32, attn_window=8,
                            layer_attn=("window", "global", "global",
                                        "window"))
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return cfg, params


def test_two_allocation_classes_in_one_pool(hybrid):
    """Global layers page by blocks, window layers by snapshots of one
    window; a slot's window state is a ring whatever ``max_len`` is."""
    cfg, params = hybrid
    eng = _engine(cfg, params, kv_max_sessions=2)
    try:
        ring = 8 + 4 + 4 - 1            # window + kv_block + steps - 1
        pool = {n: tuple(b["k"].shape) for n, b in eng._kv.pool.items()}
        assert pool == {"layer_0": (2 + 2 * 3 + 1, 4, 8, 8),
                        "layer_1": (64, 4, 8, 4), "layer_2": (64, 4, 8, 4),
                        "layer_3": (2 + 2 * 3 + 1, 4, 8, 8)}
        slabs = {n: c["cached_key"].shape[-1] for n, c in eng._cache.items()}
        assert slabs == {"layer_0": ring, "layer_1": 96, "layer_2": 96,
                         "layer_3": ring}
        stats = eng.stats()
        assert stats["kv_slot_bytes_window"] == 2 * 2 * 4 * 8 * ring * 4
        assert stats["kv_slot_bytes_global"] == 2 * 2 * 4 * 8 * 96 * 4
    finally:
        eng.stop()


def test_hybrid_second_turn_resumes_from_the_last_window(hybrid):
    """A prefix hit leaves the window layers holding exactly the last
    window of the prefix: the second turn's tokens are those of an
    engine that never had a pool, after the rings wrapped twice."""
    cfg, params = hybrid
    rng = np.random.default_rng(3)
    p1 = rng.integers(1, 97, (21,)).astype(np.int32)
    eng, cold = _engine(cfg, params), _engine(cfg, params, kv_block=0)
    try:
        out1 = eng.submit(p1, 14, session="s").result(120)
        np.testing.assert_array_equal(out1, cold.generate(p1, 14, 120))
        p2 = np.concatenate([p1, out1, np.asarray([4, 1, 9], np.int32)])
        out2 = eng.submit(p2, 9, session="s").result(120)
        np.testing.assert_array_equal(out2, cold.generate(p2, 9, 120))
        stats = eng.stats()
    finally:
        eng.stop()
        cold.stop()
    assert stats["kv_prefix_hits"] == 1, stats
    assert stats["kv_prefill_tokens_skipped"] == (21 + 13) // 4 * 4
    assert stats["kv_window_snapshots"] >= 2


def test_export_import_of_a_session_whose_window_layers_wrapped(hybrid):
    """The migration primitive with both classes: the chain's blocks
    for the global layers and the tail's one snapshot for the window
    layers travel in one blob; the adoptive engine's next turn skips
    the prefix and answers as an engine without a pool does."""
    cfg, params = hybrid
    rng = np.random.default_rng(5)
    p1 = rng.integers(1, 97, (23,)).astype(np.int32)
    eng_a = _engine(cfg, params)
    try:
        out1 = eng_a.submit(p1, 18, session="s").result(120)
        conv = np.concatenate([p1, out1])
        assert eng_a.drain(timeout=30)
        ((name, tokens, meta, blob),) = eng_a.export_sessions()
    finally:
        eng_a.stop()
    assert name == "s" and tokens == list(map(int, conv[:len(tokens)]))
    assert len(tokens) == (23 + 17) // 4 * 4       # 40: the rings wrapped
    assert meta["window"] == 8
    assert meta["ring_layers"] == ["layer_0", "layer_3"]
    per_token = 4 * 8 * 4 * 2                      # heads x dim x f32, K + V
    assert len(blob) == per_token * (2 * len(tokens) + 2 * 8)

    eng_b, cold = _engine(cfg, params), _engine(cfg, params, kv_block=0)
    try:
        assert eng_b.import_session("s", tokens, meta, blob) == 10
        p2 = np.concatenate([conv, np.asarray([4, 1], np.int32)])
        out2 = eng_b.generate(p2, 6, timeout=120)
        np.testing.assert_array_equal(out2, cold.generate(p2, 6, 120))
        stats = eng_b.stats()
        assert stats["kv_prefix_hits"] == 1, stats
        assert stats["kv_prefill_tokens_skipped"] == len(tokens), stats
        # a dense engine's chain carries no window: refused, not adopted
        with pytest.raises(ValueError, match="window layers mismatch"):
            eng_b.import_session("t", tokens, dict(meta, window=0,
                                                   ring_layers=[]), blob)
    finally:
        eng_b.stop()
        cold.stop()


def test_snapshots_are_an_lru_of_their_own(hybrid):
    """More finished requests than snapshot entries: the oldest unpinned
    holders give theirs up, a pinned session's tail keeps its own, and
    every answer is still the cold engine's."""
    cfg, params = hybrid
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 97, (n,)).astype(np.int32)
               for n in (13, 17, 11, 19, 15, 12)]
    eng = _engine(cfg, params, slots=1, kv_max_sessions=1)
    cold = _engine(cfg, params, kv_block=0)
    try:
        first = eng.submit(prompts[0], 6, session="keep").result(120)
        for p in prompts[1:]:
            np.testing.assert_array_equal(eng.generate(p, 6, 120),
                                          cold.generate(p, 6, 120))
        kv = eng._kv
        assert kv.n_snaps == 1 + 2 * 1 + 1 and kv.snaps_used() <= 3
        assert kv.chain_of("keep")[-1].snap            # pinned: kept
        p2 = np.concatenate([prompts[0], first, [5]]).astype(np.int32)
        np.testing.assert_array_equal(
            eng.submit(p2, 5, session="keep").result(120),
            cold.generate(p2, 5, 120))
        assert eng.stats()["kv_prefix_hits"] == 1
    finally:
        eng.stop()
        cold.stop()


# -- state-space layers: the snapshot class as a class of LAYER STATE (PR 32) --

@pytest.fixture(scope="module")
def recurrent():
    """Two state-space layers around one attention layer."""
    cfg = TransformerConfig(vocab_size=97, num_layers=3, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=96,
                            remat=False, dtype=jnp.float32,
                            layer_attn=("ssm", "global", "ssm"),
                            ssm_heads=4, ssm_head_dim=16, ssm_state=8,
                            ssm_chunk=8)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return cfg, params


_STATE_BYTES = 4 * 16 * 8 * 4 + 3 * (64 + 16) * 4     # S and the conv, f32


def test_the_state_class_is_allocated_by_its_bytes(recurrent, monkeypatch):
    """The attention layer pages by blocks; a state-space layer's pool
    holds whole slot states, ``n_snaps`` of them.  Without a memory
    limit one a slot (+ scratch); with one, ``slots // 2`` at least,
    and what the prefill ladder leaves, never more than one a slot."""
    from edl_tpu.serving.kv_cache import pool_device_bytes
    cfg, params = recurrent
    eng = _engine(cfg, params, slots=4, kv_max_sessions=2)
    try:
        pool = {n: {k: tuple(v.shape) for k, v in b.items()}
                for n, b in eng._kv.pool.items()}
        n = 2 + 2 * 4 + 1
        assert pool == {
            "layer_0": {"ssm/conv_state": (n, 3, 80),
                        "ssm/ssm_state": (n, 4, 16, 8)},
            "layer_1": {"k": (64, 4, 8, 4), "v": (64, 4, 4, 8)},
            "layer_2": {"ssm/conv_state": (n, 3, 80),
                        "ssm/ssm_state": (n, 4, 16, 8)}}
        stats = eng.stats()
        assert stats["kv_slot_bytes_state"] == 2 * _STATE_BYTES
        assert stats["kv_slot_bytes_window"] == 0
        one_lane = eng._cache_shapes(1)
        blocks = 2 * 64 * 4 * 8 * 4 * 4
        assert pool_device_bytes(
            one_lane, 4, 64, n_snaps=n,
            classes=eng._classes) == blocks + n * 2 * _STATE_BYTES

        class _Chip:
            device_kind = "toy chip"

            def __init__(self, limit):
                self.limit = limit

            def memory_stats(self):
                return {"bytes_limit": self.limit, "bytes_in_use": 0}

        monkeypatch.setattr(jax, "devices", lambda: [_Chip(1 << 40)])
        assert eng._require_fit(4, 4, 64, n) == 4 + 1     # one a slot
        floor = 4 // 2 + 1
        need = (4 * sum(s.size * s.dtype.itemsize
                        for s in jax.tree.leaves(one_lane))
                + blocks + floor * 2 * _STATE_BYTES)
        prefill = 1 << 30       # more than any toy prefill needs
        monkeypatch.setattr(jax, "devices", lambda: [_Chip(
            need + prefill + 2 * _STATE_BYTES + 5)])
        eng.PREFILL_KS = (1,)
        got = eng._require_fit(4, 4, 64, n)
        assert floor <= got <= 4 + 1
    finally:
        eng.stop()


def test_a_recurrent_second_turn_resumes_from_its_prompts_snapshot(recurrent):
    """A recurrence is snapshotted where the prefill was AT: the first
    prompt's last block edge.  The next turn (prompt + answer + more)
    matches blocks deeper than that, is cut back to the snapshot
    (``reusable``), prefills the rest again, and answers as an engine
    that never had a pool."""
    cfg, params = recurrent
    rng = np.random.default_rng(3)
    p1 = rng.integers(1, 97, (21,)).astype(np.int32)
    eng, cold = _engine(cfg, params), _engine(cfg, params, kv_block=0)
    try:
        out1 = eng.submit(p1, 14, session="s").result(120)
        np.testing.assert_array_equal(out1, cold.generate(p1, 14, 120))
        kv = eng._kv
        chain = kv.chain_of("s")
        assert len(chain) == (21 + 13) // 4 and [bool(nd.snap) for nd in
                                                 chain].index(True) == 4
        assert len(kv.reusable(chain)) == 5             # 20 tokens
        p2 = np.concatenate([p1, out1, np.asarray([4, 1, 9], np.int32)])
        out2 = eng.submit(p2, 9, session="s").result(120)
        np.testing.assert_array_equal(out2, cold.generate(p2, 9, 120))
        stats = eng.stats()
    finally:
        eng.stop()
        cold.stop()
    assert stats["kv_prefix_hits"] == 1, stats
    assert stats["kv_prefill_tokens_skipped"] == 20
    assert stats["kv_state_reprefill_tokens"] == 32 - 20
    assert stats["kv_state_snapshots"] == 2


def test_export_import_of_a_recurrent_session(recurrent):
    """One blob: the chain's blocks for the attention layer and the
    tail's one state snapshot for the state-space layers, down to the
    deepest node that owns one."""
    cfg, params = recurrent
    rng = np.random.default_rng(5)
    p1 = rng.integers(1, 97, (23,)).astype(np.int32)
    eng_a = _engine(cfg, params)
    try:
        out1 = eng_a.submit(p1, 10, session="s").result(120)
        conv = np.concatenate([p1, out1])
        assert eng_a.drain(timeout=30)
        ((name, tokens, meta, blob),) = eng_a.export_sessions()
    finally:
        eng_a.stop()
    assert name == "s" and tokens == list(map(int, p1[:20]))
    assert meta["state_layers"] == ["layer_0", "layer_2"]
    per_token = 4 * 8 * 4 * 2                      # heads x dim x f32, K + V
    assert len(blob) == per_token * 20 + 2 * _STATE_BYTES
    eng_b, cold = _engine(cfg, params), _engine(cfg, params, kv_block=0)
    try:
        assert eng_b.import_session("s", tokens, meta, blob) == 5
        p2 = np.concatenate([conv, np.asarray([4, 1], np.int32)])
        out2 = eng_b.generate(p2, 6, timeout=120)
        np.testing.assert_array_equal(out2, cold.generate(p2, 6, 120))
        stats = eng_b.stats()
        assert stats["kv_prefix_hits"] == 1, stats
        assert stats["kv_prefill_tokens_skipped"] == 20, stats
        with pytest.raises(ValueError, match="state layers mismatch"):
            eng_b.import_session("t", tokens, dict(meta, state_layers=[]),
                                 blob)
    finally:
        eng_b.stop()
        cold.stop()


def test_state_snapshots_share_the_snapshot_lru(recurrent):
    """More prompts than entries: the oldest unpinned holders give
    theirs up, a pinned session keeps its own, answers stay right."""
    cfg, params = recurrent
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 97, (n,)).astype(np.int32)
               for n in (13, 17, 11, 19, 15, 12)]
    eng = _engine(cfg, params, slots=1, kv_max_sessions=1)
    cold = _engine(cfg, params, kv_block=0)
    try:
        eng.submit(prompts[0], 6, session="keep").result(120)
        for p in prompts[1:]:
            np.testing.assert_array_equal(eng.generate(p, 6, 120),
                                          cold.generate(p, 6, 120))
        kv = eng._kv
        assert kv.n_snaps == 1 + 2 * 1 + 1 and kv.snaps_used() <= 3
        # the pinned session's chain still has its prompt's snapshot
        assert any(nd.snap for nd in kv.chain_of("keep"))
        p2 = np.concatenate([prompts[0], [5, 6]]).astype(np.int32)
        np.testing.assert_array_equal(eng.generate(p2, 5, 120),
                                      cold.generate(p2, 5, 120))
        assert eng.stats()["kv_prefix_hits"] == 1
    finally:
        eng.stop()
        cold.stop()


# -- latent layers: a fourth class, one head-less buffer a layer (PR 37) -------

@pytest.fixture(scope="module")
def latent():
    """A latent attention layer between two delta-rule layers."""
    cfg = TransformerConfig(vocab_size=97, num_layers=3, embed_dim=32,
                            num_heads=2, mlp_dim=64, max_len=96,
                            remat=False, dtype=jnp.float32,
                            layer_attn=("kda", "latent", "kda"),
                            kda_heads=2, kda_head_dim=16, kda_chunk=8,
                            mla_rank=24, mla_nope_dim=16, mla_rope_dim=8,
                            mla_v_dim=16)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return cfg, params


_KDA_BYTES = 2 * 16 * 16 * 4 + 3 * 3 * 32 * 4       # S and the conv, f32


def test_the_latent_class_is_one_buffer_a_layer(latent):
    """Latent rows page by blocks like a global layer's keys and
    values, but once: ``[n_blocks, block, row]``, no k and v, no head
    axis; the delta-rule layers ride the state-snapshot class."""
    from edl_tpu.serving.kv_cache import PagedKVCache, pool_device_bytes
    cfg, params = latent
    eng = _engine(cfg, params, slots=4, kv_max_sessions=2)
    try:
        pool = {n: {k: tuple(v.shape) for k, v in b.items()}
                for n, b in eng._kv.pool.items()}
        n = 2 + 2 * 4 + 1
        state = {"kda/conv_state": (n, 3, 96), "kda/kda_state": (n, 2, 16, 16)}
        assert pool == {"layer_0": state, "layer_1": {"c": (64, 4, 128)},
                        "layer_2": state}
        stats = eng.stats()
        assert stats["kv_slot_bytes_latent"] == 96 * 128 * 4
        assert stats["kv_slot_bytes_state"] == 2 * _KDA_BYTES
        assert stats["kv_slot_bytes_global"] == 0
        one_lane = eng._cache_shapes(1)
        assert pool_device_bytes(
            one_lane, 4, 64, n_snaps=n, classes=eng._classes
        ) == 64 * 4 * 128 * 4 + n * 2 * _KDA_BYTES
        # a layer that is named to no class is refused, and a mesh is
        with pytest.raises(ValueError, match="neither a state layer nor"):
            PagedKVCache(one_lane, 4, 8, 2, n_snaps=3, classes={
                name: cls for name, cls in eng._classes.items()
                if cls.snapshotted})
        from jax.sharding import Mesh
        with pytest.raises(ValueError, match="no head axis"):
            PagedKVCache({"layer_1": one_lane["layer_1"]}, 4, 8, 2,
                         classes={"layer_1": eng._classes["layer_1"]},
                         mesh=Mesh(np.asarray(jax.devices()[:2]), ("tp",)))
    finally:
        eng.stop()


def test_latent_blocks_gather_scatter_and_evict_with_parity(latent):
    """A prompt that comes again starts from its latent blocks and the
    state snapshot; a pool too small for all prompts evicts, and every
    answer equals the unpaged engine's."""
    cfg, params = latent
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 97, (n,)).astype(np.int32)
               for n in (21, 17, 26, 19, 23)]
    eng = _engine(cfg, params, slots=2, kv_pool_blocks=13)
    cold = _engine(cfg, params, kv_block=0)
    try:
        want = [cold.generate(p, 6, 120) for p in prompts]
        for p, w in zip(prompts, want):
            np.testing.assert_array_equal(eng.generate(p, 6, 120), w)
        s0 = eng.stats()
        assert s0["kv_evictions"] > 0 and s0["kv_prefix_hits"] == 0
        np.testing.assert_array_equal(eng.generate(prompts[-1], 6, 120),
                                      want[-1])
        s1 = eng.stats()
        assert s1["kv_prefix_hits"] == 1
        assert s1["kv_prefill_tokens_skipped"] == 20
    finally:
        eng.stop()
        cold.stop()


def test_export_import_of_a_latent_chain_on_a_second_pool(latent):
    """``export_chain`` carries the latent blocks and the tail's state
    snapshot; a second pool adopts them, dedups what it holds, and
    refuses a blob of another layout."""
    cfg, params = latent
    rng = np.random.default_rng(12)
    prompt = rng.integers(1, 97, (22,)).astype(np.int32)
    a, b = _engine(cfg, params), _engine(cfg, params)
    cold = _engine(cfg, params, kv_block=0)
    try:
        first = a.submit(prompt, 6, session="s").result(120)
        assert a.drain(60)
        (session, tokens, meta, blob), = a.export_sessions()
        assert len(tokens) == 20 and meta["latent_layers"] == ["layer_1"]
        assert meta["layout"]["layer_1"] == [128, "float32"]
        assert len(blob) == 5 * 4 * 128 * 4 + 2 * _KDA_BYTES
        assert b.import_session(session, tokens, meta, blob) == 5
        assert b.import_session("t", tokens, meta, blob) == 0     # dedup
        nxt = np.concatenate([prompt, first[:-1], [3, 4]]).astype(np.int32)
        np.testing.assert_array_equal(b.generate(nxt, 5, 120),
                                      cold.generate(nxt, 5, 120))
        assert b.stats()["kv_prefill_tokens_skipped"] == 20
        bad = dict(meta, layout=dict(meta["layout"], layer_1=[64, "float32"]))
        with pytest.raises(Exception, match="layout mismatch"):
            b.import_session("u", tokens, bad, blob)
        with pytest.raises(Exception, match="latent layers mismatch"):
            b.import_session("u", tokens, dict(meta, latent_layers=[]), blob)
    finally:
        b.stop()
        cold.stop()


# -- a class the pool has never heard of ----------------------------------


def test_a_fifth_cache_class_pages_without_editing_the_pool():
    """A cache class is handed to the pool, not written into it: a toy
    class (a fixed ``[5, 3]`` memory a layer, snapshotted whole out of
    the slot, under a metadata key of its own) beside a layer of
    per-head rows commits, matches, loads back into a slab, exports and
    imports into a second pool, through ``PagedKVCache``'s own methods
    and nothing else."""
    from edl_tpu.serving.cache_layout import CacheClass
    from edl_tpu.serving.kv_cache import PagedKVCache

    class Memory(CacheClass):
        kind, meta_key, snapshotted = "memory", "memory_layers", True

        def buffers(self, node):
            return {"m": ("toy/memory", None)}

        def meta(self, node):
            return list(node["toy"]["memory"].shape[1:])

    rng = np.random.default_rng(3)
    slots, hk, d, length, block = 2, 2, 4, 32, 4

    def cache_of(lanes, fill):
        return {
            "layer_0": {"cached_key": fill((lanes, hk, d, length)),
                        "cached_value": fill((lanes, hk, length, d)),
                        "cache_index": jnp.zeros((lanes,), jnp.int32)},
            "layer_1": {"toy": {"memory": fill((lanes, 5, 3))},
                        "cache_index": jnp.zeros((lanes,), jnp.int32)}}

    cache = cache_of(slots, lambda shape: jnp.asarray(
        rng.standard_normal(shape), jnp.float32))
    one_lane = jax.eval_shape(lambda: cache_of(1, jnp.zeros))
    classes = {"layer_1": Memory()}

    def pool():
        return PagedKVCache(one_lane, block, 16, 2, classes=classes,
                            n_snaps=4)

    def loaded(kv, chain, n_pad=4):
        ids = np.zeros((n_pad,), np.int32)
        ids[:len(chain)] = [nd.block_id for nd in chain]
        return kv.load_prefix_into(
            cache_of(1, jnp.zeros), kv.pool, jnp.asarray(ids), n_pad,
            jnp.asarray(len(chain) * block, jnp.int32),
            kv.snap_arg(chain[-1].snap))

    def check(slab, n):
        np.testing.assert_array_equal(
            slab["layer_0"]["cached_key"][0, :, :, :n],
            cache["layer_0"]["cached_key"][1, :, :, :n])
        np.testing.assert_array_equal(
            slab["layer_0"]["cached_value"][0, :, :n],
            cache["layer_0"]["cached_value"][1, :, :n])
        np.testing.assert_array_equal(slab["layer_1"]["toy"]["memory"][0],
                                      cache["layer_1"]["toy"]["memory"][1])
        assert int(slab["layer_1"]["cache_index"][0]) == n

    a = pool()
    assert {k: v.shape for k, v in a.pool["layer_1"].items()} == {
        "m": (4, 5, 3)}
    tokens = list(range(1, 14))                     # three full blocks
    start, new_ids, tail = a.commit(tokens)
    assert (start, len(new_ids)) == (0, 3)
    a.store_blocks(cache, 1, start, new_ids, (a.snap_for(tail), 12))
    chain = a.match(tokens + [99])
    assert [nd.block_id for nd in chain] == new_ids and chain[-1].snap
    check(loaded(a, chain), 12)
    # a chain that ends where no snapshot was taken cannot be resumed
    assert a.match(tokens[:9]) == []
    a.pin_session("s", tail)
    meta, blob = a.export_chain(a.chain_of("s"))
    assert meta["memory_layers"] == ["layer_1"] and meta["ring_layers"] == []
    assert meta["layout"]["layer_1"] == [5, 3]
    assert len(blob) == 4 * (2 * 12 * hk * d + 5 * 3)

    b = pool()
    assert b.import_chain("s", tokens[:12], meta, blob) == 3
    check(loaded(b, b.match(tokens)), 12)
    with pytest.raises(ValueError, match="memory layers mismatch"):
        PagedKVCache({"layer_0": one_lane["layer_0"]}, block, 16, 2
                     ).import_chain("s", tokens[:12],
                                    dict(meta, layers=["layer_0"]), blob)
