"""Serving fast path (ISSUE 20): mesh-sharded paged KV, chunked
prefill, speculative decoding.

Every fast-path feature is an OPTIMIZATION over the same contract the
base engine proves — greedy outputs bit-identical to
``models.generate`` — so every test here is a parity test first and a
mechanism test second: the stats must prove the fast path actually
engaged (prefix hits, chunk counts, accepted drafts), and the tokens
must prove it changed nothing.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edl_tpu.models import TransformerConfig, TransformerLM
from edl_tpu.models.generate import generate
from edl_tpu.parallel import MeshSpec, build_mesh
from edl_tpu.serving import ContinuousBatcher


@pytest.fixture(scope="module")
def small():
    cfg = TransformerConfig(vocab_size=97, num_layers=2, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=64,
                            remat=False, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return cfg, params


@pytest.fixture(scope="module")
def tp2():
    return build_mesh(MeshSpec(dp=-1, tp=2))


def _engine(cfg, params, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("temperature", 0.0)
    kw.setdefault("steps_per_sync", 2)
    kw.setdefault("kv_block", 4)
    kw.setdefault("kv_pool_blocks", 64)
    return ContinuousBatcher(cfg, params, **kw)


def _want(cfg, params, p, n):
    return np.asarray(generate(cfg, params, jnp.asarray(p[None]), n,
                               temperature=0.0))[0]


# -- mesh-sharded paged pool ----------------------------------------------


def test_mesh_pool_migration_roundtrip(small, tp2):
    """Commit → drain → export on one tp=2 engine, import into a fresh
    tp=2 engine: the sharded pool's export gathers to host layout, the
    import re-shards, and the migrated session's next turn resumes warm
    and bit-exact."""
    cfg, params = small
    p1 = np.asarray([7, 11, 13, 5, 9, 2, 8, 3], np.int32)
    eng_a = _engine(cfg, params, slots=2, mesh=tp2)
    try:
        out1 = eng_a.submit(p1, 8, session="s").result(120)
        np.testing.assert_array_equal(out1, _want(cfg, params, p1, 8))
        conv = np.concatenate([p1, out1])
        assert eng_a.drain(timeout=30)
        exported = eng_a.export_sessions()
        assert [e[0] for e in exported] == ["s"]
        _, tokens, meta, blob = exported[0]
        assert tokens == list(map(int, conv[:len(tokens)]))
    finally:
        eng_a.stop()

    eng_b = _engine(cfg, params, slots=2, mesh=tp2)
    try:
        assert eng_b.import_session("s", tokens, meta, blob) > 0
        p2 = np.concatenate([conv, np.asarray([4, 1], np.int32)])
        out2 = eng_b.generate(p2, 6, timeout=120)
        np.testing.assert_array_equal(out2, _want(cfg, params, p2, 6))
        stats = eng_b.stats()
        assert stats["kv_prefix_hits"] == 1, stats
        assert stats["kv_prefill_tokens_skipped"] == len(tokens), stats
    finally:
        eng_b.stop()


def test_mesh_paged_matches_unpaged(small, tp2):
    """The tentpole gate: one workload (shared prefixes, an unrelated
    prompt, commits in play) through a tp=2 paged engine and a tp=2
    unpaged engine — byte-identical.  (Single-device paged parity vs
    the same generate() oracle lives in test_serving_kv.py, closing
    the three-way triangle without a third engine compile.)"""
    cfg, params = small
    rng = np.random.default_rng(5)
    prefix = rng.integers(1, 97, (9,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(1, 97, (n,)).astype(np.int32)])
               for n in (2, 6, 3)]
    prompts += [rng.integers(1, 97, (5,)).astype(np.int32)]
    news = [5, 7, 4, 6]

    def run(**kw):
        eng = _engine(cfg, params, slots=2, prefill_buckets=(16,), **kw)
        try:
            return [eng.generate(p, n, timeout=120)
                    for p, n in zip(prompts, news)]
        finally:
            eng.stop()

    mesh_paged = run(mesh=tp2)
    mesh_unpaged = run(mesh=tp2, kv_block=0)
    for p, n, a, b in zip(prompts, news, mesh_paged, mesh_unpaged):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, _want(cfg, params, p, n))


# -- chunked prefill ------------------------------------------------------


def test_chunked_prefill_bit_exact_and_counted(small):
    """Prompts past ``prefill_chunk`` split into cache-aligned chunks;
    outputs identical to the unchunked engine and to generate(), and
    the chunk counters prove the split happened."""
    cfg, params = small
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 97, (n,)).astype(np.int32)
               for n in (40, 23, 6)]          # 5 + 3 + 0 chunk dispatches

    eng = _engine(cfg, params, prefill_chunk=8, prefill_buckets=(8,))
    try:
        chunked = [eng.generate(p, 5, timeout=120) for p in prompts]
        st = eng.stats()
    finally:
        eng.stop()
    # generate() is the same oracle the unchunked engine is gated
    # against, so chunked == generate() closes chunked == unchunked
    for p, a in zip(prompts, chunked):
        np.testing.assert_array_equal(a, _want(cfg, params, p, 5))
    assert st["chunked_admissions"] == 2, st
    assert st["prefill_chunks"] == 8, st    # 40 -> 5 of 8, 23 -> 3 of 8


def test_chunked_prefill_does_not_starve_decode(small):
    """The point of chunking: a live decode keeps ticking while a long
    admission prefills.  The short request (2 tokens left) must finish
    while the long one (5 chunks + 24 decode ticks) is still in
    flight — and both stay bit-exact."""
    cfg, params = small
    rng = np.random.default_rng(8)
    short = rng.integers(1, 97, (6,)).astype(np.int32)
    long = rng.integers(1, 97, (40,)).astype(np.int32)
    eng = _engine(cfg, params, prefill_chunk=8, steps_per_sync=1)
    try:
        f_short = eng.submit(short, 8)
        time.sleep(0.3)                       # short is live and decoding
        f_long = eng.submit(long, 24)
        out_short = f_short.result(120)
        long_done_at_short_finish = f_long.done()
        out_long = f_long.result(120)
        stats = eng.stats()
    finally:
        eng.stop()
    np.testing.assert_array_equal(out_short, _want(cfg, params, short, 8))
    np.testing.assert_array_equal(out_long, _want(cfg, params, long, 24))
    assert not long_done_at_short_finish
    assert stats["prefill_chunks"] >= 4, stats
    assert stats["prefill_stall_s"] >= 0.0


@pytest.fixture(params=["one_chip", "tp2"])
def mesh_or_none(request):
    return (request.getfixturevalue("tp2") if request.param == "tp2"
            else None)


def test_chunked_admission_traces_no_model_init(small, mesh_or_none,
                                                monkeypatch):
    """ISSUE 25: after construction and warm(), starting a chunked
    admission traces nothing — the cache's shape tree is kept from
    construction and the one-lane slab comes from one compiled zeros
    program.  Three admissions back to back each get their OWN buffer
    (the chunk programs donate the slab) and answer as generate()."""
    cfg, params = small
    rng = np.random.default_rng(25)
    prompts = [rng.integers(1, 97, (n,)).astype(np.int32)
               for n in (40, 23, 33)]
    eng = _engine(cfg, params, prefill_chunk=8, prefill_buckets=(8,),
                  mesh=mesh_or_none)
    try:
        eng.warm(40)
        traced = {"init": 0, "eval_shape": 0}
        init, eval_shape = type(eng._model).init, jax.eval_shape

        def counting_init(self, *a, **kw):
            traced["init"] += 1
            return init(self, *a, **kw)

        def counting_eval_shape(*a, **kw):
            traced["eval_shape"] += 1
            return eval_shape(*a, **kw)

        monkeypatch.setattr(type(eng._model), "init", counting_init)
        monkeypatch.setattr(jax, "eval_shape", counting_eval_shape)
        futs = [eng.submit(p, 5) for p in prompts]
        outs = [f.result(120) for f in futs]
        st = eng.stats()
    finally:
        eng.stop()
    # eval_shape too: flax calls it for every parameter of a model it
    # traces, so 0 says no program was (re-)traced in traffic at all
    assert traced == {"init": 0, "eval_shape": 0}, traced
    assert st["chunked_admissions"] == 3, st
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 5))


@pytest.mark.parametrize("which", ["target", "draft"])
def test_fresh_cache_matches_model_init(small, mesh_or_none, which):
    """The slab builder's output against the definition it memoises:
    leaf for leaf the shapes and dtypes of ``model.init``'s cache, all
    zeros, placed as ``_cache_shardings`` says (target) or replicated
    (draft) on the mesh — and a new buffer on every call."""
    cfg, params = small
    dcfg = TransformerConfig(vocab_size=97, num_layers=1, embed_dim=16,
                             num_heads=2, mlp_dim=32, max_len=64,
                             remat=False, dtype=jnp.float32)
    dparams = TransformerLM(dcfg).init(
        jax.random.key(1), jnp.zeros((1, 4), jnp.int32))["params"]
    eng = _engine(cfg, params, prefill_chunk=8, mesh=mesh_or_none,
                  spec_k=2, draft_cfg=dcfg, draft_params=dparams)
    try:
        model, fresh = ((eng._model, eng._fresh_cache) if which == "target"
                        else (eng._draft_model, eng._draft_fresh_cache))
        if which == "target":       # what _maybe_start_chunk calls
            slab, drops = eng._chunk_start()    # a dense stack sows nothing
            assert drops.shape == (0,) and drops.dtype == jnp.float32
            assert (jax.tree.map(lambda x: (x.shape, x.dtype, x.sharding),
                                 slab)
                    == jax.tree.map(lambda x: (x.shape, x.dtype, x.sharding),
                                    fresh(1)))
        for B in (1, 3):
            want = jax.eval_shape(lambda: model.init(
                jax.random.key(0), jnp.zeros((B, 1), jnp.int32),
                positions=jnp.zeros((B, 1), jnp.int32)))["cache"]
            got, again = fresh(B), fresh(B)
            assert jax.tree.structure(got) == jax.tree.structure(want)
            rules = (eng._cache_shardings(B) if which == "target"
                     and mesh_or_none is not None else want)
            for w, g, g2, rule in zip(*map(jax.tree.leaves,
                                           (want, got, again, rules))):
                assert (g.shape, g.dtype) == (w.shape, w.dtype)
                assert not np.asarray(g).any()
                assert g is not g2
                if mesh_or_none is not None:
                    from jax.sharding import NamedSharding, PartitionSpec
                    if which == "target":   # kv heads over tp where they divide
                        tp = dict(mesh_or_none.shape).get("tp", 1)
                        assert rule.spec == PartitionSpec(
                            *((None, "tp") if w.ndim >= 2 and tp > 1
                              and w.shape[1] % tp == 0 else ()))
                    else:
                        rule = NamedSharding(mesh_or_none, PartitionSpec())
                    assert g.sharding.is_equivalent_to(rule, g.ndim), (
                        g.sharding, rule)
    finally:
        eng.stop()


# -- speculative decoding -------------------------------------------------


def _spec_engine(cfg, params, draft_params, k, **kw):
    return _engine(cfg, params, spec_k=k, draft_cfg=cfg,
                   draft_params=draft_params, **kw)


def test_spec_self_draft_parity_and_accept_rate(small):
    """Draft == target: every proposal must verify, so the accept rate
    is ~1.0 — and the outputs are still bit-identical to generate()
    (greedy acceptance never emits an unverified token)."""
    cfg, params = small
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 97, (int(n),)).astype(np.int32)
               for n in rng.integers(3, 14, (6,))]
    eng = _spec_engine(cfg, params, params, k=3, prefill_buckets=(16,))
    try:
        outs = [eng.generate(p, 7, timeout=120) for p in prompts]
        stats = eng.stats()
    finally:
        eng.stop()
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 7))
    assert stats["spec_k"] == 3
    assert stats["spec_proposed"] > 0
    assert stats["spec_accept_rate"] > 0.9, stats


@pytest.mark.slow
def test_spec_adversarial_draft_still_bit_exact(small):
    """A randomly-initialized draft proposes garbage: near-everything
    is rejected, the engine degrades to ~1 verified token per round,
    and the outputs STILL match generate() exactly."""
    cfg, params = small
    bad_draft = TransformerLM(cfg).init(
        jax.random.key(99), jnp.zeros((1, 4), jnp.int32))["params"]
    rng = np.random.default_rng(10)
    prompts = [rng.integers(1, 97, (int(n),)).astype(np.int32)
               for n in rng.integers(3, 12, (6,))]
    eng = _spec_engine(cfg, params, bad_draft, k=3)
    try:
        outs = [eng.generate(p, 8, timeout=120) for p in prompts]
        stats = eng.stats()
    finally:
        eng.stop()
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 8))
    assert stats["spec_proposed"] > 0
    assert stats["spec_accept_rate"] < 0.9, stats


@pytest.mark.slow
def test_spec_k1_parity(small):
    cfg, params = small
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, 97, (int(n),)).astype(np.int32)
               for n in rng.integers(3, 10, (4,))]
    eng = _spec_engine(cfg, params, params, k=1)
    try:
        outs = [eng.generate(p, 7, timeout=120) for p in prompts]
    finally:
        eng.stop()
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 7))


def test_spec_eos_mid_draft_truncates(small):
    """EOS landing inside an accepted draft burst: the finish pass
    consumes round tokens in order and stops AT the eos — no trailing
    speculated tokens leak into the output."""
    cfg, params = small
    p = np.asarray([5, 9, 2], np.int32)
    ref = _want(cfg, params, p, 8)
    eos = int(ref[1])     # greedy's 2nd token: dies mid-burst at k=3
    eng = _spec_engine(cfg, params, params, k=3, eos_id=eos,
                       prefill_buckets=(8,))
    try:
        out = eng.generate(p, 8, timeout=120)
    finally:
        eng.stop()
    assert list(out) == list(ref[:2])


def test_spec_validation(small):
    cfg, params = small
    with pytest.raises(ValueError, match="draft"):
        _engine(cfg, params, spec_k=2)
    with pytest.raises(ValueError, match="greedy"):
        _spec_engine(cfg, params, params, k=2, temperature=0.7)


# -- the full stack at once -----------------------------------------------


@pytest.mark.slow
def test_mesh_chunk_spec_combined_parity(small, tp2):
    """Everything on together — tp=2 mesh, sharded paged pool, chunked
    prefill, self-draft speculation — over shared-prefix traffic with a
    long admission: bit-exact, chunks counted, drafts accepted, prefix
    reused."""
    cfg, params = small
    rng = np.random.default_rng(13)
    prefix = rng.integers(1, 97, (12,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(1, 97, (n,)).astype(np.int32)])
               for n in (3, 6)]
    prompts.append(rng.integers(1, 97, (40,)).astype(np.int32))
    eng = _engine(cfg, params, slots=2, mesh=tp2, prefill_chunk=16,
                  spec_k=2, draft_cfg=cfg, draft_params=params)
    try:
        outs = [eng.generate(p, 8, timeout=180) for p in prompts]
        stats = eng.stats()
    finally:
        eng.stop()
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _want(cfg, params, p, 8))
    assert stats["kv_prefix_hits"] >= 1, stats
    assert stats["prefill_chunks"] >= 2, stats
    assert stats["spec_accept_rate"] > 0.9, stats
