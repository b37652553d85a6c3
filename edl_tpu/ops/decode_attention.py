"""One-token decode against the KV slabs, in place: two Pallas TPU kernels.

A decode step (``L == 1``) adds one key column and one value row per
slot and attends one query per head against what the slot holds.  The
XLA lowering of that (a scatter and two einsums over the whole slabs,
``transformer.Block._decode_attention``) reads every slab whole each
token step and, on the chip, re-lays the slabs out at the jitted
program's entry and exit (the scatter wants time-major, the jit
boundary has row-major): whole-slab copies that dwarf the live KV.

Here the slabs stay in the layout the jit boundary has,
keys ``[B, Hk, D, max_len]`` and values ``[B, Hk, max_len, D]``:

- :func:`decode_append` (``pallas_call`` name ``decode_append``) writes
  each live slot's new column and row through one aliased tile of each
  slab: a 128-lane tile of K, one sublane tile of V, the tile chosen by
  the scalar-prefetched ``cache_index``.  Free slots, and writes at or
  past ``max_len``, leave their tile as it was.
- :func:`decode_attend` (``decode_attend``) is flash-decoding over the
  slabs as they lie (q ``[G, D]`` x K ``[D, tk]``, p ``[G, tk]`` x V
  ``[tk, D]``: no transpose), heads inside the block, in ONE grid step:
  ``q`` and the output whole in VMEM, the slabs left in HBM.  An XLA
  prologue (:func:`_work_list`) lists the live ``(slot, block)`` pairs,
  a slot's blocks ``0 .. (length - 1) // tk`` in order; the list and
  the lengths are scalar-prefetched, and the kernel loops over the
  list, copying each block of K and of V into one half of two buffers
  while it computes on the other (``pltpu.make_async_copy``).  What is
  on no list costs nothing: a block past a slot's length, a free slot
  (length 0, whose output rows stay zero), an empty batch.

A window layer's cache is a ring (``transformer.Block._ring_attention``:
position p at slot ``p % T``).  The same two kernels serve it: the
caller appends at ``index % T``, and :func:`decode_attend` with
``newest`` / ``visible`` attends the ``visible`` most recently written
slots ending at ``newest``, wrapping: the mask is circular, the work
list is the slab's.  In a trace a window layer's two calls are named
``window_append`` and ``window_attend``.

Precision is the einsum path's: input-dtype matmuls accumulated in
float32, scores and softmax statistics in float32, probabilities cast
to the cache dtype before the second matmul.

A MULTI-token call (a chunk, a bucketed or reuse prefill) attends the
whole slab under its mask on the einsum path: ``[H, L, max_len]``
float32 scores a lane, whatever the prefix holds.  Where those cannot
exist (:func:`prefix_tiled`: one lane's are past ``_DENSE_SCORES``; 64
heads, 256 queries and a slab of 114,688 rows are 7.5 GB)
:func:`prefix_chunk_attention` reads the slabs as they lie in tiles of
rows UP TO THE CALL'S LAST POSITION with one softmax carried across
them: an XLA loop with a dynamic trip count (``ops/latent_attention.
expanded_attention``'s pattern, PR 38), under the scope
``attn/prefix_chunk``.  A Pallas kernel was not written: the loop's
tiles are MXU-sized matmuls already, and the call is rare beside the
token steps it is timed with (PERF.md section 6, PR 52).

:func:`applies` is the dispatch rule, from what the caller can observe
and nothing else.  Off a TPU the kernels run in Pallas interpret mode
(the tier-1 parity tests); nothing selects them there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops.attention import _on_tpu

_LANES = 128
# bytes of one K (or V) block of the attend kernel at most: two slabs,
# double buffered, is four of these in VMEM beside the f32 scores
_BLOCK_BYTES = 2 << 20
# and its time steps at most.  The walk pays nothing measurable an item
# (PERF.md section 6, PR 46: 256 as fast as 512 and 1024 at long
# lengths), and the shorter the block the less of a slot's last one lies
# past its length; at 128 a K row's run is 256 B and the copies lose a
# tenth of their pace
_BLOCK_ROWS = 256
_VMEM_LIMIT = 48 << 20
_NEG = -1e30    # finite: a fully masked tail must not make inf - inf


def applies(L: int, mesh, max_len: int) -> bool:
    """Whether a decode call takes the kernels: a one-token step on a
    TPU, over slabs no mesh shards, whose time axis tiles by lanes.
    Everything else (prefill, chunks, the speculative verify, a mesh
    engine, any other backend) stays on the einsum path."""
    return (L == 1 and mesh is None and max_len % _LANES == 0
            and _on_tpu())


# One lane's dense float32 scores past which a multi-token call cannot
# attend the whole slab under a mask: an eighth of a v5e chip's memory,
# more than an engine that also holds weights, slots and a pool finds
# beside them for ONE temporary (``serving/engine._require_fit``).  The
# benchmark's other served shapes lie under it by half at least (64
# heads x 256 queries x 16,384 rows: 1.07 GB) and keep the dense path,
# program for program
_DENSE_SCORES = 2 << 30
# what one tile of :func:`prefix_chunk_attention` holds at most: its
# float32 scores and its probabilities in the cache dtype
_TILE_BYTES = 48 << 20


def prefix_tiled(L: int, H: int, max_len: int) -> bool:
    """Whether a decode model's ``L``-token call of ``H`` query heads
    against slabs of ``max_len`` rows attends the live prefix in tiles
    (:func:`prefix_chunk_attention`): where one lane's dense scores
    ``[H, L, max_len]`` in float32 are past ``_DENSE_SCORES``.  From the
    shapes alone: the model dispatches by it, the engine prices
    (``cache_layout.HeadRows.scores_bytes``) and counts
    (``model_counters``) by it, and no setting reads into it."""
    return (L > 1 and max_len % _LANES == 0
            and 4 * H * L * max_len > _DENSE_SCORES)


def prefix_block(lanes: int, L: int, H: int, max_len: int,
                 itemsize: int = 2) -> int:
    """Rows one tile of :func:`prefix_chunk_attention` reads: the
    largest power-of-two multiple of 128 that divides ``max_len`` and
    keeps the tile's ``[lanes, H, L, rows]`` float32 scores and its
    probabilities within ``_TILE_BYTES`` (512 rows for one lane of 256
    queries at 64 heads, 128 for 1024)."""
    row = lanes * H * L * (4 + itemsize)
    tk = _LANES
    while max_len % (2 * tk) == 0 and 2 * tk * row <= _TILE_BYTES:
        tk *= 2
    return tk


def prefix_chunk_attention(q, keys, values, q_pos, limit, *, scale: float):
    """``q [B, L, H, D]`` against the slabs as they lie, keys ``[B, Hk,
    D, T]`` and values ``[B, Hk, T, D]`` (the call's own rows already in
    them), query l of lane b seeing rows ``<= q_pos[b, l]``; ``limit``
    the number of leading rows that can matter (``max(q_pos) + 1``,
    traced: rows past its last tile are not read).  Grouped as the
    einsum path (query head h on head ``h // (H // Hk)``), its
    precision recipe: input-dtype matmuls accumulated in float32,
    float32 statistics, probabilities in the cache dtype.  Returns ``[B,
    L, H, D]`` in ``q``'s dtype."""
    B, L, H, D = q.shape
    Hk, T = keys.shape[1], keys.shape[3]
    tk = prefix_block(B, L, H, T, jnp.dtype(values.dtype).itemsize)
    qg = jnp.moveaxis(q.reshape(B, L, Hk, H // Hk, D), 1, 3)  # [B,Hk,G,L,D]
    f32 = jnp.float32
    see = q_pos[:, None, None, :, None]

    def tile(j, carry):
        m, l, acc = carry
        k = jax.lax.dynamic_slice_in_dim(keys, j * tk, tk, axis=3)
        v = jax.lax.dynamic_slice_in_dim(values, j * tk, tk, axis=2)
        s = jnp.einsum("bhgld,bhdt->bhglt", qg, k,
                       preferred_element_type=f32) * scale
        s = jnp.where(j * tk + jnp.arange(tk) <= see, s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        # every query sees row 0, so from the first tile on m_new is a
        # real score and a masked row's exp(_NEG - m_new) is exactly 0
        p = jnp.exp(s - m_new[..., None])
        acc = alpha[..., None] * acc + jnp.einsum(
            "bhglt,bhtd->bhgld", p.astype(values.dtype), v,
            preferred_element_type=f32)
        return m_new, alpha * l + p.sum(-1), acc

    G = H // Hk
    carry = (jnp.full((B, Hk, G, L), _NEG, f32),
             jnp.zeros((B, Hk, G, L), f32),
             jnp.zeros((B, Hk, G, L, D), f32))
    with jax.named_scope("attn/prefix_chunk"):
        tiles = -(-jnp.minimum(limit, T) // tk)
        _, l, acc = jax.lax.fori_loop(0, tiles, tile, carry)
        out = acc / jnp.where(l > 0, l, 1.0)[..., None]
        return jnp.moveaxis(out, 3, 1).reshape(B, L, H, D).astype(q.dtype)


def block_applies(L: int, mesh, max_len: int, dtype) -> bool:
    """Whether a block pass (``L`` positions a slot, written at an index
    that is a multiple of ``L``: ``TransformerConfig.block_length``)
    takes the kernels: where a one-token step does, and the block lies
    within one tile of each slab (``L`` divides a lane tile and the
    values' sublane tile)."""
    return (L > 1 and _LANES % L == 0 and _sublanes(dtype) % L == 0
            and applies(1, mesh, max_len))


def _sublanes(dtype) -> int:
    """Rows of one native tile: 8 at 32 bits, 16 at 16."""
    return 32 // jnp.dtype(dtype).itemsize


def _interpret(interpret) -> bool:
    return (not _on_tpu()) if interpret is None else interpret


# -- append ----------------------------------------------------------------

def _append_kernel(idx_ref, on_ref, k_ref, v_ref, kn_ref, vn_ref,
                   ko_ref, vo_ref, *, rows: int, width: int = 1):
    """``width`` positions from ``idx`` on (1: a token step's; more: a
    block pass's, which lie in one tile and arrive tiled over it, so
    that position p's column is at lane ``p % width`` of every run)."""
    b = pl.program_id(0)
    at, on = idx_ref[b], on_ref[b] != 0

    def within(i, start):
        return (i == start) if width == 1 else (
            (i >= start) & (i < start + width))

    lane = jax.lax.broadcasted_iota(jnp.int32, k_ref.shape, 2)
    ko_ref[...] = jnp.where(within(lane, at % _LANES) & on, kn_ref[...],
                            k_ref[...])
    row = jax.lax.broadcasted_iota(jnp.int32, v_ref.shape, 1)
    vo_ref[...] = jnp.where(within(row, at % rows) & on,
                            jnp.broadcast_to(vn_ref[...], v_ref.shape),
                            v_ref[...])


def decode_append(k_slab, v_slab, k_new, v_new, index, live, *,
                  interpret=None, ring: bool = False):
    """Write ``k_new`` / ``v_new`` ``[B, Hk, D]`` at position
    ``index[b]`` of slot b's slabs, in place (donate or carry the slabs:
    they are aliased in and out).  Slots with ``live[b]`` false or
    ``index[b] >= max_len`` are rewritten with what they held.
    ``ring`` changes the kernel's name and nothing else
    (``window_append``: a window layer's call, told apart in a trace)."""
    B, Hk, D, T = k_slab.shape
    on = (live & (index < T)).astype(jnp.int32)
    at = jnp.clip(index, 0, T - 1).astype(jnp.int32)
    # Mosaic cannot move the [Hk, D] column onto the lane axis itself;
    # XLA broadcasts it over one lane tile (B x Hk x D x 128 elements)
    kn = jnp.broadcast_to(k_new.astype(k_slab.dtype)[..., None],
                          (B, Hk, D, _LANES))
    vn = v_new.astype(v_slab.dtype)[:, :, None, :]
    return _append(k_slab, v_slab, kn, vn, at, on, 1,
                   "window_append" if ring else "decode_append", interpret)


def block_append(k_slab, v_slab, k_new, v_new, index, live, *,
                 interpret=None):
    """:func:`decode_append` for a block pass (``block_append``): write
    ``k_new`` / ``v_new`` ``[B, L, Hk, D]`` at positions ``index[b] ..
    index[b] + L - 1`` of slot b's slabs, in place.  ``index[b]`` is a
    multiple of ``L`` and ``L`` divides both tiles
    (:func:`block_applies`), so the block lies in one tile of each slab.
    Slots with ``live[b]`` false, or whose block would reach past
    ``max_len``, are rewritten with what they held."""
    B, Hk, D, T = k_slab.shape
    L = k_new.shape[1]
    rows = _sublanes(v_slab.dtype)
    on = (live & (index + L <= T)).astype(jnp.int32)
    at = jnp.clip(index, 0, T - L).astype(jnp.int32)
    # the block tiled over the tile: position p's column at every lane
    # (row) congruent to p, of which the kernel keeps the run at `at`
    kn = jnp.tile(k_new.astype(k_slab.dtype).transpose(0, 2, 3, 1),
                  (1, 1, 1, _LANES // L))
    vn = jnp.tile(v_new.astype(v_slab.dtype).transpose(0, 2, 1, 3),
                  (1, 1, rows // L, 1))
    return _append(k_slab, v_slab, kn, vn, at, on, L, "block_append",
                   interpret)


def _append(k_slab, v_slab, kn, vn, at, on, width: int, name: str,
            interpret):
    """The append ``pallas_call``: ``kn [B, Hk, D, 128]`` and ``vn [B,
    Hk, 1 or rows, D]`` through the tiles ``at`` names."""
    _, Hk, D, _ = k_slab.shape
    rows = _sublanes(v_slab.dtype)
    k_spec = pl.BlockSpec((None, Hk, D, _LANES),
                          lambda b, at, on: (b, 0, 0, at[b] // _LANES))
    v_spec = pl.BlockSpec((None, Hk, rows, D),
                          lambda b, at, on: (b, 0, at[b] // rows, 0))
    return pl.pallas_call(
        functools.partial(_append_kernel, rows=rows, width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(k_slab.shape[0],),
            in_specs=[
                k_spec, v_spec,
                pl.BlockSpec((None, Hk, D, _LANES),
                             lambda b, at, on: (b, 0, 0, 0)),
                pl.BlockSpec((None, Hk, vn.shape[2], D),
                             lambda b, at, on: (b, 0, 0, 0))],
            out_specs=[k_spec, v_spec]),
        out_shape=[jax.ShapeDtypeStruct(k_slab.shape, k_slab.dtype),
                   jax.ShapeDtypeStruct(v_slab.shape, v_slab.dtype)],
        # operands count the two prefetched scalars
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(interpret),
        name=name,
    )(at, on, k_slab, v_slab, kn, vn)


# -- attend ----------------------------------------------------------------

def _attend_kernel(len_ref, slot_ref, blk_ref, nw_ref, *refs, tk: int,
                   scale: float, ring: int = 0):
    """The whole call: ``q`` and the output whole in VMEM, the slabs in
    HBM.  A loop over the work list (:func:`_work_list`) copies each
    live block of K ``[Hk, D, tk]`` and of V ``[Hk, tk, D]`` into one
    half of two buffers a slab, the next item's copies in flight while
    this one is computed; a slot's statistics start at its first block
    and its output rows are written at its last.  ``ring`` (the slab's
    length, static) adds two prefetched scalars a slot, the newest ring
    slot and how many slots back are visible; 0 is the plain slab,
    masked by length."""
    if ring:
        newest_ref, visible_ref, *refs = refs
    (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, m_ref, l_ref,
     acc_ref) = refs
    nw = nw_ref[0]

    def copies(i):
        b = slot_ref[i]
        at = pl.ds(pl.multiple_of(blk_ref[i] * tk, tk), tk)
        return (pltpu.make_async_copy(k_hbm.at[b, :, :, at],
                                      k_buf.at[i % 2], sem.at[0, i % 2]),
                pltpu.make_async_copy(v_hbm.at[b, :, at, :],
                                      v_buf.at[i % 2], sem.at[1, i % 2]))

    def start(i):
        for c in copies(i):
            c.start()

    # a free slot is on no list: its rows stay zero
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(nw > 0)
    def _():
        start(0)

    def item(i, carry):
        b, j = slot_ref[i], blk_ref[i]
        n = len_ref[b]

        @pl.when(i + 1 < nw)
        def _():
            start(i + 1)

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        for c in copies(i):
            c.wait()
        q, k, v = q_ref[b], k_buf[i % 2], v_buf[i % 2]
        # [Hk, Gp, D] x [Hk, D, tk] -> [Hk, Gp, tk], f32
        s = jax.lax.dot_general(
            q, k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        pos = j * tk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        if ring:
            back = newest_ref[b] - pos
            seen = jnp.where(back < 0, back + ring, back) < visible_ref[b]
        else:
            seen = pos < n
        s = jnp.where(seen, s, _NEG)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        # a listed block holds a live position, so m_new is a real
        # score and the masked tail's exp(_NEG - m_new) is exactly 0
        p = jnp.exp(s - m_new)
        if ring:
            # a ring's block may hold no visible slot (the window lies
            # in the other block): m_new is then _NEG and exp(0) is 1
            p = jnp.where(seen, p, 0.0)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        # [Hk, Gp, tk] x [Hk, tk, D] -> [Hk, Gp, D]
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        # the slot's last block: the list is in slot order
        @pl.when((j + 1) * tk >= n)
        def _():
            l = l_ref[...]
            o_ref[b] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
                o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, nw, item, 0)


def _work_list(lengths, tk: int, nb: int):
    """What the attend kernel walks: the live ``(slot, block)`` pairs in
    slot order, each slot's blocks ``0 .. (len - 1) // tk`` ascending,
    and how many there are (``[1]``).  The lists have room for every
    block of every slot (``B * nb``); entries past the count are
    unused."""
    blocks = (lengths + tk - 1) // tk
    ends = jnp.cumsum(blocks)
    item = jnp.arange(lengths.shape[0] * nb, dtype=jnp.int32)
    # the slots that end at or before an item are those before its own
    before = item[:, None] >= ends[None, :]
    slot = jnp.minimum(before.sum(axis=1), lengths.shape[0] - 1)
    block = item - jnp.where(before, blocks[None, :], 0).sum(axis=1)
    return (slot.astype(jnp.int32), block.astype(jnp.int32),
            ends[-1:].astype(jnp.int32))


def _fetch_plan(lengths, tk: int):
    """Per slot, the slot and the block range its grid steps fetch from.
    A live slot reads its own blocks ``0 .. (len - 1) // tk``; a free
    slot names the block the step before it left in VMEM (its last live
    predecessor's last block, or block 0 of the first live slot when it
    has none), which the pipeline then does not fetch again."""
    B = lengths.shape[0]
    live = lengths > 0
    slot = jnp.arange(B, dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(live, slot, -1))
    src = jnp.where(prev >= 0, prev, jnp.argmax(live).astype(jnp.int32))
    last = jnp.where(live, (lengths - 1) // tk, 0)
    hi = jnp.where(prev >= 0, last[src], 0)
    lo = jnp.where(live, 0, hi)
    return src, lo.astype(jnp.int32), hi.astype(jnp.int32)


def blocks_fetched(src, lo, hi, nb: int):
    """Blocks a grid over (slots, ``nb`` blocks) fetches under a plan
    ``(src, lo, hi)`` (:func:`_fetch_plan`'s shape), float32: the
    pipeline fetches a block when a grid step names another than the
    step before it, so the count is the changes of block along the
    grid, its first step included."""
    j = jnp.arange(nb, dtype=jnp.int32)[None]
    named = (src[:, None] * nb
             + jnp.clip(j, lo[:, None], hi[:, None])).reshape(-1)
    return (1 + jnp.sum(named[1:] != named[:-1])).astype(jnp.float32)


def attend_block(Hk: int, D: int, max_len: int, dtype) -> int:
    """Time steps one item of the attend kernel's walk reads: the
    largest power-of-two multiple of 128, ``_BLOCK_ROWS`` at most, that
    divides ``max_len`` and keeps one K block (all heads) within
    ``_BLOCK_BYTES``."""
    tk = _LANES
    while (2 * tk <= _BLOCK_ROWS and max_len % (2 * tk) == 0
           and 2 * tk * Hk * D * jnp.dtype(dtype).itemsize <= _BLOCK_BYTES):
        tk *= 2
    return tk


def tokens_fetched(lengths, Hk: int, D: int, max_len: int, dtype,
                   kernel: bool):
    """Positions one attend call fetches of the slots' slabs, float32:
    on the kernel's path whole blocks up to each live slot's length
    (the list the kernel walks, :func:`_work_list`, times the block), on
    the einsum path every slot's slab."""
    if not kernel:
        return jnp.asarray(lengths.shape[0] * max_len, jnp.float32)
    tk = attend_block(Hk, D, max_len, dtype)
    *_, nw = _work_list(lengths.astype(jnp.int32), tk, max_len // tk)
    return nw[0].astype(jnp.float32) * tk


def decode_attend(q, k_slab, v_slab, lengths, *, newest=None, visible=None,
                  block: int | None = None, interpret=None,
                  scale: float | None = None, name: str | None = None):
    """Attention of one query per head, ``q [B, H, D]``, against the
    first ``lengths[b]`` positions of slot b's slabs; ``[B, H, D]`` in
    ``q``'s dtype.  Query head h reads KV head ``h // (H // Hk)``.  A
    slot of length 0 reads nothing and returns zeros.  ``scale``
    multiplies the scores (None = ``D ** -0.5``).

    With ``newest`` and ``visible`` (``[B]`` int) the slabs are rings:
    slot b attends the ``visible[b]`` slots that end, wrapping, at ring
    slot ``newest[b]``; ``lengths[b]`` is then how many ring slots have
    been written at all (what is fetched; 0 = a free slot), and the
    kernel is named ``window_attend``.  ``name`` names the kernel
    otherwise (a block pass's call, ``block_attend``: ``q`` then holds a
    KV head's ``L * G`` query rows side by side, all against one key
    set)."""
    assert (newest is None) == (visible is None)
    _, Hk, D, T = k_slab.shape
    tk = block or attend_block(Hk, D, T, k_slab.dtype)
    assert T % tk == 0, (T, tk)
    return _attend(q, k_slab, v_slab, lengths, newest, visible, tk,
                   _interpret(interpret),
                   D ** -0.5 if scale is None else scale,
                   name or ("decode_attend" if newest is None
                            else "window_attend"))


# a program's layers are alike: jitted, the kernel is traced and lowered
# once a program, not once a layer (ops/moe._decode_gmm)
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _attend(q, k_slab, v_slab, lengths, newest, visible, tk: int,
            interpret: bool, scale: float, name: str):
    ring = newest is not None
    B, H, D = q.shape
    _, Hk, _, T = k_slab.shape
    G = H // Hk
    # the group axis is the matmuls' row axis: pad it to a sublane tile
    Gp = -(-G // _sublanes(q.dtype)) * _sublanes(q.dtype)
    qg = jnp.pad(q.reshape(B, Hk, G, D), ((0, 0), (0, 0), (0, Gp - G),
                                          (0, 0)))
    lengths = lengths.astype(jnp.int32)
    extra = ((newest.astype(jnp.int32), visible.astype(jnp.int32))
             if ring else ())
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_attend_kernel, tk=tk, scale=scale,
                          ring=T if ring else 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 + len(extra), grid=(1,),
            in_specs=[whole, hbm, hbm],
            out_specs=whole,
            scratch_shapes=[pltpu.VMEM((2, Hk, D, tk), k_slab.dtype),
                            pltpu.VMEM((2, Hk, tk, D), v_slab.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((Hk, Gp, 1), jnp.float32),
                            pltpu.VMEM((Hk, Gp, 1), jnp.float32),
                            pltpu.VMEM((Hk, Gp, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hk, Gp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(lengths, *_work_list(lengths, tk, T // tk), *extra, qg, k_slab, v_slab)
    return out[:, :, :G].reshape(B, H, D)
