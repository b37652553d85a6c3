"""The one-token decode kernels (edl_tpu/ops/decode_attention.py), and
every program this repo compiles for a DESCRIBED chip.

On the CPU the two ``pallas_call``s run in interpret mode.  Nothing
selects them there, so every test that wants the kernel path patches
the dispatch predicate (``decode_attention.applies``) itself: there is
no setting to flip.  The reference is the einsum branch of
``transformer.Block._decode_attention``, reached through the same
model with the predicate left alone.

The last section compiles for v5e without a chip: the engine's decode
step, and (PR 29) the trainer's fsdp=4 step.  Both live in this one
file because one process may load libtpu, and the test runner gives a
file to one worker.
"""

import contextlib
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from edl_tpu.models import TransformerConfig, TransformerLM
from edl_tpu.ops import decode_attention
from edl_tpu.serving import ContinuousBatcher

MAX_LEN, BLOCK = 256, 128
RING, WINDOW = 256, 128     # a window layer's ring, below
# slot -> (cache_index before the step, live): a free slot first, in the
# middle and last; lengths 1, one under / at / over a block edge,
# max_len - 1, max_len, and an index past the end (write dropped)
SLOTS = {
    "free_first": (5, False),
    "len_1": (0, True),
    "len_block_minus_1": (BLOCK - 2, True),
    "len_block": (BLOCK - 1, True),
    "free_middle": (BLOCK + 7, False),
    "len_block_plus_1": (BLOCK, True),
    "len_max_minus_1": (MAX_LEN - 2, True),
    "len_max": (MAX_LEN - 1, True),
    "index_past_end": (MAX_LEN, True),
    "free_last": (MAX_LEN - 1, False),
}
NAMES = list(SLOTS)
INDEX = np.array([SLOTS[n][0] for n in NAMES], np.int32)
LIVE = np.array([SLOTS[n][1] for n in NAMES])


def _force_kernels(monkeypatch, block=None):
    """Take the kernel path wherever its shapes rule holds, TPU or not
    (interpret mode here), optionally at a smaller attend block."""
    monkeypatch.setattr(decode_attention, "applies",
                        lambda L, mesh, max_len: L == 1 and mesh is None)
    if block:
        monkeypatch.setattr(decode_attention, "attend_block",
                            lambda *a: block)


@functools.lru_cache(maxsize=None)
def _one_step(G: int, dtype_name: str):
    """One decode token step of a one-layer model over a cache full of
    stale data, on the einsum path and on the kernel path."""
    dtype = jnp.dtype(dtype_name)
    Hk, D, B = 2, 16, len(NAMES)
    cfg = TransformerConfig(vocab_size=61, num_layers=1, embed_dim=Hk * G * D,
                            num_heads=Hk * G, num_kv_heads=Hk, mlp_dim=32,
                            max_len=MAX_LEN, remat=False, dtype=dtype,
                            decode=True, attention_impl="dense")
    model = TransformerLM(cfg)
    ids = jnp.asarray(np.arange(B)[:, None] % 61, jnp.int32)
    init = model.init(jax.random.key(0), ids,
                      positions=jnp.zeros((B, 1), jnp.int32))
    kk, kv = jax.random.split(jax.random.key(1))
    layer = init["cache"]["layer_0"]
    cache = {"layer_0": {
        "cached_key": jax.random.normal(kk, layer["cached_key"].shape,
                                        dtype),
        "cached_value": jax.random.normal(kv, layer["cached_value"].shape,
                                          dtype),
        "cache_index": jnp.asarray(INDEX)}}

    def step():
        return model.apply(
            {"params": init["params"], "cache": cache}, ids,
            positions=jnp.asarray(INDEX)[:, None],
            token_mask=jnp.asarray(LIVE)[:, None], mutable=["cache"])

    want, want_mut = step()
    with pytest.MonkeyPatch.context() as mp:
        _force_kernels(mp, BLOCK)
        got, got_mut = step()
    to_np = functools.partial(jax.tree.map, lambda a: np.asarray(
        a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a))
    return (to_np(cache["layer_0"]), np.asarray(want),
            to_np(want_mut["cache"]["layer_0"]), np.asarray(got),
            to_np(got_mut["cache"]["layer_0"]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("G", [4, 1], ids=["gqa4", "mha"])
@pytest.mark.parametrize("slot", NAMES)
def test_kernel_step_matches_the_einsum_step(slot, G, dtype):
    before, want, want_cache, got, got_cache = _one_step(G, dtype)
    b = NAMES.index(slot)
    at, live = SLOTS[slot]
    assert got_cache["cache_index"][b] == at + 1
    k, v = got_cache["cached_key"][b], got_cache["cached_value"][b]
    if not live or at >= MAX_LEN:
        # a free slot, or a write past the end: the slab is untouched
        np.testing.assert_array_equal(k, before["cached_key"][b])
        np.testing.assert_array_equal(v, before["cached_value"][b])
    else:
        # exactly the einsum path's slab: its new column, nothing else
        np.testing.assert_array_equal(k, want_cache["cached_key"][b])
        np.testing.assert_array_equal(v, want_cache["cached_value"][b])
        rest = np.arange(MAX_LEN) != at
        np.testing.assert_array_equal(k[:, :, rest],
                                      before["cached_key"][b][:, :, rest])
        np.testing.assert_array_equal(v[:, rest],
                                      before["cached_value"][b][:, rest])
        assert (k[:, :, at] != before["cached_key"][b][:, :, at]).any()
    assert np.isfinite(got[b]).all()
    if live:    # a free slot's output is ignored
        tol = 1e-5 if dtype == "float32" else 3e-2
        np.testing.assert_allclose(got[b], want[b], atol=tol, rtol=tol)


def test_a_batch_of_free_slots_reads_nothing_and_returns_zeros():
    q = jnp.ones((3, 4, 16), jnp.float32)
    k = jnp.full((3, 2, 16, MAX_LEN), jnp.nan, jnp.float32)
    v = jnp.full((3, 2, MAX_LEN, 16), jnp.nan, jnp.float32)
    out = decode_attention.decode_attend(
        q, k, v, jnp.zeros((3,), jnp.int32), block=BLOCK)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@pytest.mark.parametrize("lengths,src,lo,hi", [
    ([0, 300, 0, 0, 129, 0], [1, 1, 1, 1, 4, 4], [0, 0, 2, 2, 0, 1],
     [0, 2, 2, 2, 1, 1]),
    ([0, 0, 0], [0, 0, 0], [0, 0, 0], [0, 0, 0]),
    ([128, 1], [0, 1], [0, 0], [0, 0]),
])
def test_fetch_plan_points_free_slots_at_the_block_already_held(
        lengths, src, lo, hi):
    got = decode_attention._fetch_plan(jnp.asarray(lengths, jnp.int32),
                                       BLOCK)
    assert [np.asarray(a).tolist() for a in got] == [src, lo, hi]


@pytest.mark.parametrize("Hk,max_len,dtype,want", [
    (8, 8192, jnp.bfloat16, 256),       # the Mistral serve cells
    (16, 4096, jnp.bfloat16, 256),      # the OLMoE serve cell
    (2, 384, jnp.float32, 128),         # 384 = 3 x 128: no larger divisor
    (10, 20480, jnp.bfloat16, 256),     # Phi-4-mini-flash's full layer
    (10, 640, jnp.bfloat16, 128),       # and its rings: 640 = 5 x 128
    (64, 8192, jnp.float32, 128),       # 256 steps of 64 heads: 4 MiB
])
def test_attend_block_divides_max_len_within_the_vmem_budget(
        Hk, max_len, dtype, want):
    assert decode_attention.attend_block(Hk, 128, max_len, dtype) == want


# -- the walk over the live blocks (PR 46) -----------------------------------
# lengths a slot: 0 = free.  Two blocks of 128 a slot
WALKS = {
    "free_first_between_last": [0, 1, 0, BLOCK, BLOCK + 1, 0, MAX_LEN, 0],
    "every_slot_live": [MAX_LEN, 1, BLOCK, BLOCK + 1, 7, MAX_LEN - 1, 2,
                        BLOCK - 1],
    "one_live_slot_at_the_last_index": [0, 0, 0, 0, 0, 0, 0, BLOCK + 1],
    "one_live_slot_at_the_first_index": [1, 0, 0, 0, 0, 0, 0, 0],
    "nothing_live": [0] * 8,
}


@pytest.mark.parametrize("case", list(WALKS))
def test_work_list_holds_the_live_blocks_in_slot_order(case):
    lengths = WALKS[case]
    nb = MAX_LEN // BLOCK
    slot, block, nw = (np.asarray(a) for a in decode_attention._work_list(
        jnp.asarray(lengths, jnp.int32), BLOCK, nb))
    want = [(b, j) for b, n in enumerate(lengths)
            for j in range(-(-n // BLOCK))]
    assert slot.shape == block.shape == (len(lengths) * nb,)
    assert nw.tolist() == [len(want)]
    assert list(zip(slot[:len(want)].tolist(),
                    block[:len(want)].tolist())) == want
    # what lies past the count names a slot all the same
    assert ((slot >= 0) & (slot < len(lengths))).all()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(decode_attention, "attend_block", lambda *a: BLOCK)
        fetched = decode_attention.tokens_fetched(
            jnp.asarray(lengths), 2, 16, MAX_LEN, jnp.float32, True)
    assert float(fetched) == len(want) * BLOCK
    assert float(decode_attention.tokens_fetched(
        jnp.asarray(lengths), 2, 16, MAX_LEN, jnp.float32,
        False)) == len(lengths) * MAX_LEN


@functools.lru_cache(maxsize=None)
def _walked(ring: bool):
    """``decode_attend`` jitted once a kind of slab, and its operands:
    every case of a kind is another set of lengths of one program."""
    B, H, Hk, D = 8, 4, 2, 16
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, Hk, D, MAX_LEN))
    v = jax.random.normal(ks[2], (B, Hk, MAX_LEN, D))

    def attend(written):
        if not ring:
            return decode_attention.decode_attend(q, k, v, written,
                                                  block=BLOCK)
        return decode_attention.decode_attend(
            q, k, v, jnp.minimum(written, MAX_LEN), block=BLOCK,
            newest=(written - 1) % MAX_LEN,
            visible=jnp.minimum(written, WINDOW))

    return jax.jit(attend), np.asarray(q), np.asarray(k), np.asarray(v)


# positions written a slot so far (0 = free), into a ring of 256 with a
# window of 128: inside the first window, the window sliding, the ring
# just full, the append wrapped, the window across the seam, many laps
RING_WALKS = {
    "not_wrapped": [0, 1, 0, WINDOW, WINDOW + 1, 0, MAX_LEN, 0],
    "wrapped": [MAX_LEN + 1, 0, MAX_LEN + 45, 2 * MAX_LEN, 0,
                5 * MAX_LEN + 131, 3, MAX_LEN + WINDOW],
    "one_live_slot_at_the_last_index": [0] * 7 + [MAX_LEN + 45],
}


@pytest.mark.parametrize("kind,case", [
    *(("slab", c) for c in WALKS), *(("ring", c) for c in RING_WALKS)])
def test_walk_matches_plain_attention_over_what_a_slot_sees(kind, case):
    ring = kind == "ring"
    written = (RING_WALKS if ring else WALKS)[case]
    attend, q, k, v = _walked(ring)
    got = np.asarray(attend(jnp.asarray(written, jnp.int32)))
    for b, n in enumerate(written):
        if n == 0:      # a free slot is on no list: zeros
            np.testing.assert_array_equal(got[b], 0.0)
            continue
        seen = ((n - 1 - np.arange(min(n, WINDOW))) % MAX_LEN if ring
                else np.arange(n))
        for h in range(q.shape[1]):
            s = q[b, h] @ k[b, h // 2][:, seen] * q.shape[-1] ** -0.5
            p = np.exp(s - s.max())
            np.testing.assert_allclose(
                got[b, h], (p / p.sum()) @ v[b, h // 2][seen],
                atol=2e-5, rtol=2e-5)


def test_dispatch_rule_is_shape_mesh_and_backend_only(monkeypatch):
    applies = decode_attention.applies
    assert not applies(1, None, 256)            # this backend is no TPU
    monkeypatch.setattr(decode_attention, "_on_tpu", lambda: True)
    assert applies(1, None, 256)
    assert not applies(2, None, 256)            # verify pass, prefill
    assert not applies(1, object(), 256)        # a mesh engine's slabs
    assert not applies(1, None, 200)            # time axis not lane-tiled


def _toy_engine_tokens(monkeypatch, kernels: bool):
    cfg = TransformerConfig(vocab_size=97, num_layers=2, embed_dim=32,
                            num_heads=4, num_kv_heads=2, mlp_dim=64,
                            max_len=128, remat=False, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    if kernels:
        _force_kernels(monkeypatch)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 97, (n,)).astype(np.int32)
               for n in (5, 9, 3)]
    eng = ContinuousBatcher(cfg, params, slots=2, prefill_buckets=(8, 16),
                            temperature=0.0, steps_per_sync=4, kv_block=0,
                            prefill_chunk=0)
    try:
        # two slots, three requests: the 3-token answer finishes inside
        # the first program and its slot is re-admitted while the other
        # slot is still mid-answer
        futs = [eng.submit(p, n) for p, n in zip(prompts, (8, 3, 8))]
        out = [f.result(timeout=300).tolist() for f in futs]
        stats = eng.stats()
    finally:
        eng.stop()
    return out, stats


def test_toy_engine_gives_the_einsum_paths_tokens_through_a_readmission(
        monkeypatch):
    want, _ = _toy_engine_tokens(monkeypatch, kernels=False)
    got, stats = _toy_engine_tokens(monkeypatch, kernels=True)
    assert got == want and [len(t) for t in got] == [8, 3, 8]
    # the counters say what a step has to read, and what the slabs hold
    assert 0 < stats["decode_kv_tokens_live"] < stats["decode_kv_tokens_slab"]
    assert stats["decode_kv_tokens_slab"] % (2 * 128 * 4) == 0


# -- a window layer's ring (PR 30) ------------------------------------------
# the same two kernels over a ring of 256 positions and a window of 128:
# slot -> (cache_index before the step, live).  Position p lives at ring
# slot p % 256; the step appends at index % 256 and attends the last
# min(index + 1, 128) positions, wrapping
RING_SLOTS = {
    "first_token": (0, True),
    "inside_the_first_window": (5, True),
    "window_just_full": (WINDOW - 1, True),
    "window_slides": (WINDOW, True),
    "free_before_the_wrap": (200, False),
    "last_slot_of_the_ring": (RING - 1, True),
    "append_wraps_to_slot_0": (RING, True),
    "window_spans_the_seam": (RING + 44, True),
    "free_after_the_wrap": (RING + 77, False),
    "second_lap_ends": (2 * RING - 1, True),
    "many_laps": (5 * RING + 131, True),
}
RING_NAMES = list(RING_SLOTS)
RING_INDEX = np.array([RING_SLOTS[n][0] for n in RING_NAMES], np.int32)
RING_LIVE = np.array([RING_SLOTS[n][1] for n in RING_NAMES])


@functools.lru_cache(maxsize=None)
def _one_ring_step(G: int, dtype_name: str):
    """One decode token step of a one-layer WINDOW model over a ring
    full of stale data, on the einsum path and on the kernel path (two
    attend blocks of 128, so a window may lie in one block only)."""
    dtype = jnp.dtype(dtype_name)
    Hk, D, B = 2, 16, len(RING_NAMES)
    cfg = TransformerConfig(vocab_size=61, num_layers=1, embed_dim=Hk * G * D,
                            num_heads=Hk * G, num_kv_heads=Hk, mlp_dim=32,
                            max_len=8 * RING, remat=False, dtype=dtype,
                            decode=True, attention_impl="dense",
                            attn_window=WINDOW, window_ring=RING)
    model = TransformerLM(cfg)
    ids = jnp.asarray(np.arange(B)[:, None] % 61, jnp.int32)
    init = model.init(jax.random.key(0), ids,
                      positions=jnp.zeros((B, 1), jnp.int32))
    layer = init["cache"]["layer_0"]
    assert layer["cached_key"].shape == (B, Hk, D, RING)
    kk, kv = jax.random.split(jax.random.key(1))
    cache = {"layer_0": {
        "cached_key": jax.random.normal(kk, layer["cached_key"].shape,
                                        dtype),
        "cached_value": jax.random.normal(kv, layer["cached_value"].shape,
                                          dtype),
        "cache_index": jnp.asarray(RING_INDEX)}}

    def step():
        return model.apply(
            {"params": init["params"], "cache": cache}, ids,
            positions=jnp.asarray(RING_INDEX)[:, None],
            token_mask=jnp.asarray(RING_LIVE)[:, None], mutable=["cache"])

    want, want_mut = step()
    with pytest.MonkeyPatch.context() as mp:
        _force_kernels(mp, BLOCK)
        got, got_mut = step()
    to_np = functools.partial(jax.tree.map, lambda a: np.asarray(
        a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a))
    return (to_np(cache["layer_0"]), np.asarray(want),
            to_np(want_mut["cache"]["layer_0"]), np.asarray(got),
            to_np(got_mut["cache"]["layer_0"]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("G", [4, 1], ids=["gqa4", "mha"])
@pytest.mark.parametrize("slot", RING_NAMES)
def test_ring_kernel_step_matches_the_einsum_step(slot, G, dtype):
    before, want, want_cache, got, got_cache = _one_ring_step(G, dtype)
    b = RING_NAMES.index(slot)
    at, live = RING_SLOTS[slot]
    assert got_cache["cache_index"][b] == at + 1 == (
        want_cache["cache_index"][b])
    k, v = got_cache["cached_key"][b], got_cache["cached_value"][b]
    # the einsum path's ring exactly: a live slot's new column at
    # index % ring and nothing else, a free slot's ring untouched
    np.testing.assert_array_equal(k, want_cache["cached_key"][b])
    np.testing.assert_array_equal(v, want_cache["cached_value"][b])
    rest = np.arange(RING) != at % RING if live else np.full(RING, True)
    np.testing.assert_array_equal(k[:, :, rest],
                                  before["cached_key"][b][:, :, rest])
    np.testing.assert_array_equal(v[:, rest],
                                  before["cached_value"][b][:, rest])
    if live:
        assert (k[:, :, at % RING]
                != before["cached_key"][b][:, :, at % RING]).any()
        tol = 1e-5 if dtype == "float32" else 3e-2
        np.testing.assert_allclose(got[b], want[b], atol=tol, rtol=tol)
    assert np.isfinite(got[b]).all()


def test_ring_attend_sees_the_window_and_nothing_older():
    """``decode_attend`` in ring mode against plain softmax attention
    over the ``visible`` most recent ring slots, for a window that lies
    in one block, spans both, and wraps; moving a key OUTSIDE the
    window changes nothing, moving one inside it does."""
    B, H, Hk, D = 4, 4, 2, 16
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, Hk, D, RING))
    v = jax.random.normal(ks[2], (B, Hk, RING, D))
    newest = jnp.asarray([40, 150, 20, RING - 1], jnp.int32)
    visible = jnp.asarray([41, 128, 128, 128], jnp.int32)
    held = jnp.asarray([41, 151, RING, RING], jnp.int32)

    def attend(k, v):
        return np.asarray(decode_attention.decode_attend(
            q, k, v, held, newest=newest, visible=visible, block=BLOCK,
            interpret=True))

    got = attend(k, v)
    for b in range(B):
        slots = (int(newest[b]) - np.arange(int(visible[b]))) % RING
        kb, vb = np.asarray(k[b])[:, :, slots], np.asarray(v[b])[:, slots]
        for h in range(H):
            s = np.asarray(q[b, h]) @ kb[h // 2] * D ** -0.5
            p = np.exp(s - s.max())
            np.testing.assert_allclose(got[b, h], (p / p.sum()) @ vb[h // 2],
                                       atol=2e-5, rtol=2e-5)
    # slot 0's window is ring slots 0..40; slot 2's wraps: 149..255, 0..20
    outside = attend(k.at[:, :, :, 41].set(9.0).at[2, :, :, 100].set(9.0), v)
    np.testing.assert_array_equal(outside[[0, 2]], got[[0, 2]])
    inside = attend(k.at[2, :, :, 200].set(9.0), v)
    assert np.abs(inside[2] - got[2]).max() > 1e-3


# -- compiled for the chip, without the chip ---------------------------------

@contextlib.contextmanager
def _no_compile_cache():
    """An AOT executable can be written to a compile cache but not read
    back without a chip: keep it out (and the warning with it)."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1))
    except Exception as e:  # noqa: BLE001 — any failure means: not here
        pytest.skip(f"no v5e:1x1 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_step_program_for_v5e_holds_no_whole_slab_temporary(
        one_chip, monkeypatch):
    """``ContinuousBatcher._step_impl`` at the Mistral serve widths (two
    layers, 12 slots x 8192 tokens, 4 token steps), compiled ahead of
    time for one v5e chip from abstract shapes: no engine, no slabs on
    this host.  The einsum path compiled the same way keeps 0.81 GB of
    temporaries, a second copy of every slab, and re-lays each slab out
    at the program's entry and exit; a read kernel beside an XLA scatter
    moves those copies INSIDE the token loop.  Neither may come back."""
    import types

    from edl_tpu.models.generate import sample_logits

    # what applies() asks of the backend, answered for the described chip
    monkeypatch.setattr(decode_attention, "_on_tpu", lambda: True)
    B, T, Hk, max_len = 12, 4, 8, 8192
    cfg = TransformerConfig(
        vocab_size=32768, num_layers=2, embed_dim=4096, num_heads=32,
        num_kv_heads=Hk, mlp_dim=14336, max_len=max_len, rope_theta=1e6,
        norm_eps=1e-5, decode=True, attention_impl="dense")
    model = TransformerLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((B, 1), jnp.int32),
        positions=jnp.zeros((B, 1), jnp.int32)))

    def on_chip(s, dtype=None):
        return jax.ShapeDtypeStruct(s.shape, dtype or s.dtype,
                                    sharding=one_chip)

    # the part of an engine the step reads, and nothing it would allocate
    engine = types.SimpleNamespace(
        _model=model, _T=T,
        _acc_shape=jax.ShapeDtypeStruct((0,), jnp.float32),
        _sown=lambda mut: jnp.zeros((0,), jnp.float32),
        _positions=ContinuousBatcher._positions,
        _sample=lambda logits, key: sample_logits(logits, key,
                                                  temperature=0.0))

    def _step_impl(*args):
        return ContinuousBatcher._step_impl(engine, *args)

    with _no_compile_cache():
        compiled = jax.jit(_step_impl, donate_argnums=(0,)).lower(
            jax.tree.map(on_chip, shapes["cache"]),
            on_chip(jax.ShapeDtypeStruct((B,), jnp.int32)),
            on_chip(jax.eval_shape(lambda: jax.random.key(0))),
            jax.tree.map(lambda s: on_chip(s, jnp.bfloat16),
                         shapes["params"]),
            on_chip(jax.ShapeDtypeStruct((B,), jnp.bool_))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6
    slab = re.compile(rf"\[{B},{Hk},(128,{max_len}|{max_len},128)\]")
    moved = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) "
                     r"([\w\-]+)\(", line)
        if not m or not slab.search(m.group(2)):
            continue
        name, op = m.group(1), m.group(3)
        # plumbing moves nothing; the append kernel's results ARE its
        # arguments (aliased)
        if op in ("parameter", "get-tuple-element", "tuple", "while",
                  "bitcast") or (op == "custom-call"
                                 and name.startswith("decode_append")):
            continue
        moved.append(f"{op} {name}")
    assert not moved, moved
    text = compiled.as_text()
    assert "decode_append" in text and "decode_attend" in text


@pytest.mark.parametrize("B,Hk,G,T,ring", [
    (32, 10, 4, 20480, False),      # Phi-4-mini-flash's full layer
    (32, 10, 4, 640, True),         # and a window layer's ring
    (12, 8, 4, 8192, False),        # the Mistral serve cells
])
def test_attend_kernel_compiles_for_v5e_at_published_widths(
        one_chip, B, Hk, G, T, ring):
    """``ops/decode_attention.decode_attend`` at the served slabs,
    compiled ahead of time for one v5e chip from abstract shapes: the
    Mosaic compiler takes the walk (the loop over the list, the copies
    of a block of K and of V out of slabs in HBM into two halves, the
    dynamic slot of ``q`` and of the output), and the program keeps the
    padded queries and the output beside it and no copy of a slab."""
    D, bf = 128, jnp.bfloat16

    def sds(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(q, k, v, n, newest, visible):
        extra = dict(newest=newest, visible=visible) if ring else {}
        return decode_attention.decode_attend(q, k, v, n, interpret=False,
                                              **extra)

    ints = sds((B,), jnp.int32)
    with _no_compile_cache():
        compiled = jax.jit(attend).lower(
            sds((B, Hk * G, D)), sds((B, Hk, D, T)), sds((B, Hk, T, D)),
            ints, ints, ints).compile()
    text = compiled.as_text()
    assert ("window_attend" if ring else "decode_attend") in text
    assert "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4e6


def test_ssm_step_kernel_compiles_for_v5e_at_published_widths(one_chip):
    """``ops/ssm.ssm_step`` at granite-4.0-h-small's widths (32 slots,
    128 heads of 64, state 128), compiled ahead of time for one v5e
    chip from abstract shapes: the Mosaic compiler takes the kernel,
    the states are aliased in and out (no second copy: 134 MB each),
    and the step's own rows go in head-minor, not padded to a lane
    tile a head (a [B, H, P, 1] operand alone is 134 MB of padding)."""
    from edl_tpu.ops import ssm

    B, H, P, N = 32, 128, 64, 128

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with _no_compile_cache():
        compiled = jax.jit(
            lambda s, x, dt, A, b, c, live: ssm.ssm_step(
                s, x, dt, A, b, c, live, interpret=False),
            donate_argnums=(0,)).lower(
                sds((B, H, P, N)), sds((B, H, P)), sds((B, H)), sds((H,)),
                sds((B, 1, N)), sds((B, 1, N)), sds((B,), jnp.bool_)
        ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == B * H * P * N * 4
    assert mem.temp_size_in_bytes < 8e6
    assert "ssm_step" in compiled.as_text()


def test_mamba1_step_kernel_compiles_for_v5e_at_published_widths(one_chip):
    """``ops/mamba1.mamba1_step`` at Phi-4-mini-flash-reasoning's widths
    (32 slots, inner 5120, state 16), compiled ahead of time for one v5e
    chip from abstract shapes: the Mosaic compiler takes the kernel (a
    ``[16, 1]`` column broadcast over the state's lanes, a ``[1, 5120]``
    row over its sublanes), and the states are aliased in and out."""
    from edl_tpu.ops import mamba1

    B, N, Di = 32, 16, 5120

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with _no_compile_cache():
        compiled = jax.jit(
            lambda s, x, dt, A, b, c, live: mamba1.mamba1_step(
                s, x, dt, A, b, c, live, interpret=False),
            donate_argnums=(0,)).lower(
                sds((B, N, Di)), sds((B, Di), jnp.bfloat16), sds((B, Di)),
                sds((N, Di)), sds((B, N), jnp.bfloat16),
                sds((B, N), jnp.bfloat16), sds((B,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == B * N * Di * 4
    assert mem.temp_size_in_bytes < 2e6
    assert "mamba1_step" in compiled.as_text()


def test_kda_and_latent_kernels_compile_for_v5e_at_published_widths(
        one_chip):
    """``ops/kda.kda_step``, ``ops/latent_attention.latent_append`` and
    ``latent_attend`` at Kimi-Linear's widths (32 slots of 32768
    positions, 32 heads of 128 x 128 state, rows of 640 values),
    compiled ahead of time for one v5e chip: the Mosaic compiler takes
    the kernels, the states and the rows are aliased in and out, and
    nothing copies a slab (a row of 576 values, not in whole lane tiles,
    compiled too, with a 1.3 GB relayout of the rows a call: why the
    cache keeps 640)."""
    from edl_tpu.ops import kda
    from edl_tpu.ops import latent_attention as la

    B, H, R, T, W = 32, 32, 128, 32768, 640

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf = jnp.bfloat16
    with _no_compile_cache():
        step = jax.jit(
            lambda s, q, k, v, g, b, live: kda.kda_step(
                s, q, k, v, g, b, live, interpret=False),
            donate_argnums=(0,)).lower(
                sds((B, H, R, R)), sds((B, H, R)), sds((B, H, R)),
                sds((B, H, R)), sds((B, H, R)), sds((B, H)),
                sds((B,), jnp.bool_)).compile()
        append = jax.jit(
            lambda rows, new, at, live: la.latent_append(
                rows, new, at, live, interpret=False),
            donate_argnums=(0,)).lower(
                sds((B, T, W), bf), sds((B, W), bf), sds((B,), jnp.int32),
                sds((B,), jnp.bool_)).compile()
        attend = jax.jit(
            lambda q, rows, n: la.latent_attend(
                q, rows, n, scale=192 ** -0.5, interpret=False)).lower(
                sds((B, H, W), bf), sds((B, T, W), bf),
                sds((B,), jnp.int32)).compile()
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes == B * H * R * R * 4
    assert mem.temp_size_in_bytes < 8e6 and "kda_step" in step.as_text()
    mem = append.memory_analysis()
    assert mem.alias_size_in_bytes == B * T * W * 2
    assert mem.temp_size_in_bytes < 8e6
    assert "latent_append" in append.as_text()
    assert attend.memory_analysis().temp_size_in_bytes < 8e6
    assert "latent_attend" in attend.as_text()
    assert la.padded_width(576) == W
    assert la.attend_block(W, T, bf) == 512


@pytest.mark.parametrize("lanes", [1, 4])
def test_expanded_latent_path_for_v5e_holds_a_tile_of_scores(one_chip, lanes):
    """``ops/latent_attention.expanded_attention`` at Kimi-Linear's
    widths and the chunk lane's shape (256 tokens a lane against 32768
    rows of 640 values, 32 heads), compiled ahead of time for one v5e
    chip: its temporaries are a tile's (the whole slab's float32 scores
    are 1 GiB a lane) and its loop over tiles has no constant trip
    count."""
    from edl_tpu.ops import latent_attention as la

    L, H, T, W = 256, 32, 32768, 640
    bf = jnp.bfloat16

    def sds(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def chunk(q, rows, w_kvb, idx):
        return la.expanded_attention(
            q, rows, w_kvb, idx[:, None] + jnp.arange(L), idx.max() + L,
            rank=512, nope=128, scale=192 ** -0.5)

    with _no_compile_cache():
        compiled = jax.jit(chunk).lower(
            sds((lanes, L, H, 192)), sds((lanes, T, W)),
            sds((512, H, 256), jnp.float32),
            sds((lanes,), jnp.int32)).compile()
    assert 128 <= la.expand_block(lanes, L, H, 256, T, bf) < T
    # scores and probabilities, the expanded tile, the carried statistics
    assert compiled.memory_analysis().temp_size_in_bytes < (
        2 * la._TILE_BYTES + lanes * 2 * L * H * 128 * 4 + (8 << 20))
    text = compiled.as_text()
    assert not re.search(rf"f32\[[0-9,]*{L},{T}\]", text)
    loops = [ln for ln in text.splitlines() if re.search(r"= .* while\(", ln)]
    assert loops and not any("known_trip_count" in ln for ln in loops)


@pytest.mark.parametrize("lanes,L,H", [
    (1, 512, 128),      # openPangu-Ultra-MoE's chunk
    (8, 256, 128),      # and its widest bucketed prefill
    (1, 256, 32),       # Kimi-Linear's chunk
    (4, 256, 32),       # and its widest bucketed prefill
])
def test_expanded_latent_kernel_compiles_for_v5e_at_published_widths(
        one_chip, lanes, L, H):
    """``ops/latent_attention.latent_expand_tiled`` at the two served
    latent configurations' multi-token shapes (32768 rows of 640
    values, rank 512, heads of 128 + 64 key and 128 value dims),
    compiled ahead of time for one v5e chip with a traced limit: the
    Mosaic compiler takes the kernel (its VMEM budget, its aligned
    slices), and what the program keeps in HBM beside its arguments is
    the head-major queries and the output: no float32 scores, no
    expanded rows, no loop."""
    from edl_tpu.ops import latent_attention as la

    T, W, kv = 32768, 640, 256
    bf = jnp.bfloat16

    def sds(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def chunk(q, rows, w_kvb, idx):
        return la.latent_expand_tiled(
            q, rows, w_kvb, idx[:, None] + jnp.arange(L), idx.max() + L,
            rank=512, nope=128, scale=192 ** -0.5, interpret=False)

    with _no_compile_cache():
        compiled = jax.jit(chunk).lower(
            sds((lanes, L, H, 192)), sds((lanes, T, W)), sds((512, H, kv)),
            sds((lanes,), jnp.int32)).compile()
    tk = la.expand_block(lanes, L, H, kv, T, bf, True)
    assert tk == 1024 and la.expand_heads(H, kv, tk, L) == 4
    text = compiled.as_text()
    assert "latent_expand_tiled" in text and "tpu_custom_call" in text
    # the queries padded to a row's rest (256 wide) and the output, twice
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        2 * lanes * L * H * (256 + 128) * 2 + (1 << 20))
    assert not re.search(r"f32\[[0-9,]*,(128|256|512|1024)\]\{", text)
    assert not re.search(r"= .* while\(", text)


@pytest.mark.parametrize("rows,M,H,E,chunks", [
    (320, 4096, 768, 36, (1024, 384)),    # granite-4.0-h-small, 32 x top-10
    (96, 2048, 1024, 64, (1024, 1024)),   # OLMoE, 12 slots x top-8
    (96, 6144, 2048, 16, (512, 256)),     # K-EXAONE, 12 x top-8, 16 held
])
def test_expert_decode_kernel_compiles_for_v5e_at_published_widths(
        one_chip, rows, M, H, E, chunks):
    """``ops/moe.decode_gmm`` at the three served expert configurations'
    decode shapes, compiled ahead of time for one v5e chip from abstract
    shapes: the Mosaic compiler takes the kernel (the dynamic row
    windows, the loops over touched experts and over their chunks, the
    chunk copies from HBM into two buffers a projection), and the
    program keeps nothing but the padded rows beside it."""
    from edl_tpu.ops import moe

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert moe.gmm_chunks(M, H, True, jnp.bfloat16) == chunks
    with _no_compile_cache():
        compiled = jax.jit(lambda *a: moe.decode_gmm(
            *a, interpret=False)).lower(
                sds((rows, M)), sds((E,), jnp.int32), sds((E, M, H)),
                sds((E, M, H)), sds((E, H, M))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8e6
    assert "moe_decode_gmm" in compiled.as_text()


@pytest.mark.parametrize("T,K,held,E,M,H,bound", [
    (512, 8, 16, 256, 7680, 2048, 848),     # openPangu-Ultra-MoE, a chunk
    (256, 8, 16, 128, 6144, 2048, 1040),    # K-EXAONE, a chunk
    (256, 8, 64, 256, 2304, 1024, 2048),    # Kimi-Linear: every row fits
    (32, 10, 36, 72, 4096, 768, 320),       # granite, a bucket of 32 alone
    (256, 10, 36, 72, 4096, 768, 1824),     # granite, a chunk: half live
])
def test_expert_prefix_kernel_compiles_for_v5e_at_published_widths(
        one_chip, monkeypatch, T, K, held, E, M, H, bound):
    """``ops/moe.prefix_gmm`` over the bound ``prefix_rows`` gives the
    held configurations' multi-token calls, compiled ahead of time for
    one v5e chip: the rows in and out whole in VMEM beside the float32
    result, the hidden rows and the weight chunks, within what the
    kernel asks of the chip's 128 MiB."""
    from edl_tpu.ops import moe

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    assert moe.prefix_rows(T, K, held, E, M, H, jnp.bfloat16) == bound
    with _no_compile_cache():
        compiled = jax.jit(lambda *a: moe.prefix_gmm(
            *a, interpret=False)).lower(
                sds((bound, M)), sds((held,), jnp.int32), sds((held, M, H)),
                sds((held, M, H)), sds((held, H, M))).compile()
    assert "moe_prefix_gmm" in compiled.as_text()


@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001 — any failure means: not here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _compile_train_step(devices, spec, batch, monkeypatch, **widths):
    """``ElasticTrainer``'s own step (a ``TransformerLM`` of ``widths``,
    4096 tokens a row, remat, unrolled, splash, fused CE in blocks of
    4096, adamw) compiled ahead of time for the described ``devices``
    from abstract shapes: ``(compiled, abstract state)``."""
    import optax

    from edl_tpu.models import transformer as tf_mod
    from edl_tpu.models.logical import logical_axes_from_paths
    from edl_tpu.ops import attention
    from edl_tpu.parallel.sharding import logical_sharding
    from edl_tpu.train import ElasticTrainer, TrainConfig

    # what "auto" asks of the backend, answered for the described chips
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    seq, ce_block = 4096, 4096
    cfg = TransformerConfig(
        vocab_size=32768, num_layers=2, max_len=seq, rope_theta=1e6,
        attention_impl="auto", remat=True, scan_layers=False, **widths)
    lm = TransformerLM(cfg)

    def loss_fn(params, extra, batch, rng):
        h = lm.apply({"params": params}, batch["ids"][:, :-1],
                     return_hidden=True)
        return tf_mod.lm_loss_fused(params, h, batch["ids"][:, 1:], cfg,
                                    block_size=ce_block), (extra, {})

    def init():
        return lm.init(jax.random.key(0),
                       jnp.zeros((len(devices), 8), jnp.int32))["params"], None

    trainer = ElasticTrainer(
        loss_fn, TrainConfig(mesh_spec=spec, global_batch_size=batch,
                             log_every=0), devices=devices)
    logical = logical_axes_from_paths(jax.eval_shape(lambda: init()[0]),
                                      tf_mod.LOGICAL_RULES)
    key = jax.eval_shape(lambda: jax.random.key(0))
    with _no_compile_cache():
        state = trainer._abstract_state(init, optax.adamw(3e-4), logical)
        compiled = trainer.step_fn.lower(
            state,
            {"ids": jax.ShapeDtypeStruct(
                (batch, seq + 1), jnp.int32,
                sharding=logical_sharding(("batch", None), trainer.mesh))},
            jax.ShapeDtypeStruct(
                key.shape, key.dtype,
                sharding=logical_sharding((), trainer.mesh)),
        ).compile()
    return compiled, state


def test_fsdp_train_step_for_v5e_gathers_weights_not_activations(
        four_chips, monkeypatch):
    """``ElasticTrainer``'s own step at the Codestral widths of the
    four-chip training cell (two layers, fsdp=4, 1 x 4096 tokens a
    chip, remat, unrolled, splash, fused CE in blocks of 4096), compiled
    ahead of time for a v5e 2x2 host from abstract shapes.  Before
    ``transformer._pin`` GSPMD kept every weight's ``fsdp`` shard in
    place and moved the activations: ``all-reduce bf16[4,4096,16384]``
    after each MLP matmul, ``all-reduce f32[16576,4096]`` of the logits
    blocks in the CE loop, whole-batch all-gathers and all-to-alls.  Now
    no collective carries the whole batch, and the weights travel, in
    bf16 (PERF.md section 6, PR 29)."""
    from edl_tpu.parallel import MeshSpec, build_mesh
    from tests.helpers.hlo import (collectives, squeezed,
                                   whole_batch_collectives)

    chips, seq = len(four_chips), 4096
    spec = MeshSpec(dp=1, fsdp=chips)
    compiled, state = _compile_train_step(
        four_chips, spec, chips, monkeypatch, embed_dim=6144, num_heads=48,
        num_kv_heads=8, mlp_dim=16384,
        mesh=build_mesh(spec, four_chips))      # the trainer builds the same
    text = compiled.as_text()
    # what one chip holds of each weight, a layer at a time
    shards = {squeezed(p.sharding.shard_shape(p.shape)[p.ndim - 2:])
              for p in jax.tree.leaves(state.params) if p.ndim >= 2}
    assert whole_batch_collectives(text, chips, seq, weights=shards) == []
    moved = [dtype for _, dtype, dims in collectives(text)
             if squeezed(dims) in shards]
    assert len(moved) >= 10 and set(moved) == {"bf16"}
    # the head is split, so the loss keeps the loop over blocks of the
    # vocabulary, which gathers one block at a time (PR 51)
    assert "ce/vocab_blocks" in text and "ce/sweep" not in text
    # the parent's step kept 4.57 GB of temporaries at this depth.  This
    # one does not keep fewer, as ISSUE 29 expected: the scheduler holds
    # the next matmuls' weight windows in flight (the peak measured on
    # the chip did not move: 10.29 GB on both sides)
    assert compiled.memory_analysis().temp_size_in_bytes < 5.3e9


def test_one_chip_train_step_for_v5e_keeps_norms_out_of_its_matmuls(
        one_chip, monkeypatch):
    """``ElasticTrainer``'s own step at the Mistral widths of the
    one-chip training cell (two layers, 4 x 4096 tokens, remat,
    unrolled, splash, fused CE in blocks of 4096, adamw), compiled ahead
    of time for one v5e chip from abstract shapes.  Left to choose, XLA
    put each norm's reductions into the matmuls beside it: on the parent
    of PR 47 ``matmul_fusions_with_reduce`` names ``fusion.116`` /
    ``.119`` (the backward of ``mlp_gate`` with the whole backward of
    ``mlp_norm``: 22.0 ms each on the chip, 45% of the peak),
    ``fusion.130`` / ``.140`` (``mlp_out`` with the next norm's sum of
    squares: 15.4 ms, 64%), ``fusion.135`` / ``.143`` (the backward of
    ``attn_qkv`` with that of ``attn_norm``) and ``fusion.136`` /
    ``.144`` (``attn_out`` with ``mlp_norm``'s sum of squares), and
    ``fusion.374``, the head block's matmul with the CE's row max, which
    lies under neither ``layers/`` nor ``final_norm`` and stays.  With
    the norms fenced (``transformer._fences_norms``) no layer matmul
    carries a reduce (PERF.md section 6, PR 47)."""
    from edl_tpu.parallel import MeshSpec
    from tests.helpers.hlo import matmul_fusions_with_reduce

    compiled, _ = _compile_train_step(
        list(one_chip.device_set), MeshSpec(dp=1, fsdp=1), 4, monkeypatch,
        embed_dim=4096, num_heads=32, num_kv_heads=8, mlp_dim=14336)
    text = compiled.as_text()
    assert matmul_fusions_with_reduce(
        text, under=("layers/", "final_norm")) == []
    # the helper still sees a matmul with a reduce where one is: the CE's
    assert len(matmul_fusions_with_reduce(text)) == 1
    # the head is whole: one sweep over blocks of tokens, which multiplies
    # by it three times (logits, dhidden, dW) and leaves the backward no
    # matmul (PERF.md section 6, PR 51)
    head = [line.split('op_name="')[1].split('"')[0]
            for line in text.splitlines()
            if " convolution(" in line and "ce/" in line]
    assert len(head) == 3 and all(
        name.startswith("jit(step)/jvp(ce/sweep)/while/body/")
        for name in head)
    assert "ce/vocab_blocks" not in text
    # 6.806 GB on the parent, 6.865 GB fenced: the norms' outputs are
    # whole arrays now; 6.873 GB with the head swept (of the chip's
    # 16.9 GB the state takes 8.456)
    assert compiled.memory_analysis().temp_size_in_bytes < 7.0e9
