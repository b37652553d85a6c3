"""Multi-head latent attention over a head-less cache: the expanded path,
the absorbed path, and the three kernels.

A latent layer caches, a token, ONE row ``(c, k_pe)`` of ``rank + rope``
values (``c`` the normed low-rank key/value latent, ``k_pe`` the few
extra key dims every head shares; with no rotation they are just more
dims): no head axis, keys and values the same bytes.  With ``W_kvb``
``[rank, H, nope + v]`` = ``W_K | W_V`` the attention of head h is::

    k_nope_h, v_h = W_K[:, h]^T c, W_V[:, h]^T c
    score_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) * scale
    o_h     = sum_t softmax(score_h)_t v_h,t

Two ways to compute it, equal in exact arithmetic:

- **expanded** (scope ``attn/latent_expand``) - ``k_nope | v`` are
  computed from the latent rows and plain attention runs over them:
  multi-token calls (a full forward, a prefill, a chunk against the
  slot's rows and its own).  It runs flash-style over tiles of rows,
  one softmax carried across them (running maximum, sum and a float32
  accumulator), up to the call's last position and no further: the trip
  count follows the cache index, so a chunk against a long slab reads
  the rows it can see.  :func:`expanded_attention` is that as an XLA
  loop, whose tile's float32 scores and expanded rows pass through HBM
  (within ``_TILE_BYTES`` whatever the slab's length is): every other
  backend's path, a mesh's, the differentiated forward's, and the
  kernel's parity reference.  On the chip a decode model's multi-token
  call takes :func:`latent_expand_tiled` (the ``pallas_call`` of that
  name: the grid over lanes and blocks of heads, a block's queries and
  its slice of ``W_kvb`` resident, a loop inside the kernel over the
  tiles up to the scalar-prefetched limit; a tile of rows is copied as
  it lies in the cache, expanded on the MXU inside the kernel for the
  block's heads, met by ALL the call's queries once, and neither the
  expanded rows nor the scores leave VMEM).  :func:`expand_block` says
  how many rows a tile of the path that runs holds.
- **absorbed** (:func:`absorb` / :func:`unabsorb`, scope
  ``attn/latent_absorb``) - ``W_K`` moves onto the query (``q~_h =
  W_K[:, h] q_nope_h``, ``rank`` wide) and ``W_V`` onto the output
  (``o_h = W_V[:, h]^T sum p c``), so the scores and the value sum read
  the latent rows as they lie, once for all heads: one-token steps.  On
  the chip two Pallas kernels run it in place: :func:`latent_append`
  (one row a live slot through one aliased sublane tile) and
  :func:`latent_attend` (flash-decoding over the rows: the grid over
  slots and position tiles, a tile fetched ONCE for all heads' scores
  and the value sum; lengths scalar-prefetched, so a tile past a slot's
  length and a free slot fetch nothing:
  ``ops/decode_attention._fetch_plan``).  :func:`latent_attend_reference`
  is the same as einsums over the whole slab: every other backend's path
  and the kernels' parity reference.

Which call takes which: a one-token step ``latent_append`` +
``latent_attend`` where :func:`applies` holds; a decode model's
multi-token call ``latent_expand_tiled`` where :func:`expand_applies`
holds; everything else the einsums and the loop.  Both rules are
functions of what the caller can observe and nothing else.  Precision is
the slabs' (``ops/decode_attention``) on every path: input-dtype matmuls
accumulated in float32, scores and softmax statistics in float32,
probabilities cast to the cache dtype before the second matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops import decode_attention
from edl_tpu.ops.attention import _on_tpu
from edl_tpu.ops.decode_attention import (
    _NEG, _VMEM_LIMIT, _fetch_plan, _interpret, _sublanes,
)

_LANES = 128
# bytes of one tile of latent rows of the attend kernel (double buffered)
_BLOCK_BYTES = 1 << 20
# float32 scores and expanded keys and values of one tile of the
# expanded path's XLA loop (in HBM)
_TILE_BYTES = 48 << 20
# of the expanded path's kernel (in VMEM): one head's float32 scores of
# a tile, and one head block's float32 expansion of it, a block at
# least ``_MIN_HEADS`` heads (a tile's copy and one matmul serve them)
_SCORE_BYTES = 2 << 20
_EXPAND_BYTES = 4 << 20
_MIN_HEADS = 4


def applies(L: int, mesh, max_len: int) -> bool:
    """Whether a call takes the kernels (``ops/decode_attention.
    applies``: a one-token step on a TPU, no mesh, a lane-tiled time
    axis)."""
    return decode_attention.applies(L, mesh, max_len)


def expand_applies(L: int, mesh, T: int, row: int, dtype, *widths) -> bool:
    """Whether a multi-token call takes the kernel
    (:func:`latent_expand_tiled`) and not the XLA loop
    (:func:`expanded_attention`): on a TPU, no mesh, ``L`` whole sublane
    tiles of ``dtype`` whose scores against one lane tile of rows fit
    the kernel's budget, and the slab's length ``T``, its ``row`` and
    every one of ``widths`` (the rank and the heads' nope and value
    widths: the kernel slices rows and expansions there) whole lane
    tiles."""
    return (mesh is None and L > 1 and L % _sublanes(dtype) == 0
            and 4 * L * _LANES <= _SCORE_BYTES
            and not any(n % _LANES for n in (T, row, *widths)) and _on_tpu())


def padded_width(width: int) -> int:
    """The row a latent cache keeps for ``width`` values: whole lane
    tiles (576 -> 640).  The device's tiled layout pads the minor
    dimension to that in any case; kept explicit, the kernels' blocks
    and matmuls are aligned and the bytes are the same."""
    return -(-width // _LANES) * _LANES


def cache_rows(latent, row: int, dtype):
    """``latent [..., width]`` as the cache keeps it: the cache's dtype,
    zeros up to ``row`` values."""
    pad = [(0, 0)] * (latent.ndim - 1) + [(0, row - latent.shape[-1])]
    return jnp.pad(latent.astype(dtype), pad)


# -- expanded ---------------------------------------------------------------

def expand_block(lanes: int, L: int, H: int, kv: int, T: int, dtype,
                 kernel: bool = False) -> int:
    """Rows one tile of the expanded path reads of a ``T``-row slab for
    ``lanes`` calls of ``L`` queries and ``H`` heads, on the path that
    runs.  The XLA loop's: the largest power-of-two multiple of 128
    that divides ``T`` and keeps what the tile holds - its ``[lanes, H,
    L, rows]`` float32 scores and the rows' keys and values expanded for
    every head, ``kv = nope + v`` of ``dtype`` a head - within
    ``_TILE_BYTES``; a slab that is not whole lane tiles is one tile.
    The ``kernel``'s (a lane and a block of heads at a time, so neither
    counts): the largest such multiple that keeps one head's ``[L,
    rows]`` float32 scores within ``_SCORE_BYTES`` and ``_MIN_HEADS``
    heads' float32 expansion of the rows within ``_EXPAND_BYTES`` (1024
    rows for a chunk of 256 or 512 queries: the chip's sweep, PERF.md
    section 6, PR 42)."""
    if T % _LANES:
        return T
    if kernel:
        def fits(tk):
            return (4 * L * tk <= _SCORE_BYTES
                    and 4 * _MIN_HEADS * kv * tk <= _EXPAND_BYTES)
    else:
        row = lanes * H * (4 * L + kv * jnp.dtype(dtype).itemsize)

        def fits(tk):
            return tk * row <= _TILE_BYTES
    tk = _LANES
    while T % (2 * tk) == 0 and fits(2 * tk):
        tk *= 2
    return tk


def expand_heads(H: int, kv: int, tk: int, L: int) -> int:
    """Heads a grid step of the kernel holds: the largest power of two
    that divides ``H`` and keeps the float32 expansion of a tile of
    ``tk`` rows for them (one matmul), and what stays resident of them
    across the tiles (``L`` queries a head and their accumulator),
    within ``_EXPAND_BYTES``."""
    hb = 1
    while H % (2 * hb) == 0 and 2 * hb * max(tk, L) * kv * 4 <= _EXPAND_BYTES:
        hb *= 2
    return hb


def expanded_attention(q, latent, w_kvb, q_pos, limit, *, rank: int,
                       nope: int, scale: float):
    """``q [B, L, H, nope + rope]``, ``latent [B, T, >= rank + rope]``
    (``c | k_pe`` leading each row), ``w_kvb [rank, H, nope + v]``,
    ``q_pos [B, L]`` (query l of lane b sees rows ``<= q_pos[b, l]``),
    ``limit`` the number of leading rows that can matter (``max(q_pos) +
    1``; a Python int for a static trip count, a traced scalar for a
    dynamic one: rows past its last tile are not read).  Returns ``[B,
    L, H, v]`` in ``q``'s dtype."""
    B, L, H, Dq = q.shape
    T = latent.shape[1]
    rope = Dq - nope
    tk = expand_block(B, L, H, w_kvb.shape[-1], T, latent.dtype)
    qh = jnp.moveaxis(q, 2, 1)                             # [B, H, L, Dq]
    q_nope, q_pe = qh[..., :nope], qh[..., nope:]
    w = w_kvb.astype(latent.dtype)
    f32 = jnp.float32

    def tile(j, carry):
        m, l, acc = carry
        rows = jax.lax.dynamic_slice_in_dim(latent, j * tk, tk, axis=1)
        with jax.named_scope("attn/latent_expand"):
            kv = jnp.einsum("btc,chd->bhtd", rows[..., :rank], w)
        s = (jnp.einsum("bhld,bhtd->bhlt", q_nope, kv[..., :nope],
                        preferred_element_type=f32)
             + jnp.einsum("bhld,btd->bhlt", q_pe, rows[..., rank:rank + rope],
                          preferred_element_type=f32)) * scale
        pos = j * tk + jnp.arange(tk)
        s = jnp.where(pos <= q_pos[:, None, :, None], s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        # every query sees row 0, so from the first tile on m_new is a
        # real score and a masked row's exp(_NEG - m_new) is exactly 0
        p = jnp.exp(s - m_new[..., None])
        acc = alpha[..., None] * acc + jnp.einsum(
            "bhlt,bhtd->bhld", p.astype(latent.dtype), kv[..., nope:],
            preferred_element_type=f32)
        return m_new, alpha * l + p.sum(-1), acc

    carry = (jnp.full((B, H, L), _NEG, f32), jnp.zeros((B, H, L), f32),
             jnp.zeros((B, H, L, w_kvb.shape[-1] - nope), f32))
    static = isinstance(limit, int)
    tiles = -(-(min(limit, T) if static else jnp.minimum(limit, T)) // tk)
    if static and tiles == 1:
        _, l, acc = tile(0, carry)
    else:
        _, l, acc = jax.lax.fori_loop(0, tiles, tile, carry)
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)


def _expand_kernel(lim_ref, q_ref, pos_ref, w_ref, lat_hbm, o_ref, buf, sem,
                   m_ref, l_ref, acc_ref, kv_ref, *, tk: int, hb: int, rank: int,
                   nope: int, rope: int, scale: float):
    b = pl.program_id(0)
    T = lat_hbm.shape[1]
    kv = w_ref.shape[1] // hb
    tiles = pl.cdiv(jnp.minimum(lim_ref[0], T), tk)
    m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def fetch(j, slot):
        return pltpu.make_async_copy(
            lat_hbm.at[b, pl.ds(pl.multiple_of(j * tk, tk), tk)],
            buf.at[slot], sem.at[slot])

    @pl.when(tiles > 0)
    def _():
        fetch(0, 0).start()

    def tile(j, _):
        slot = j % 2

        @pl.when(j + 1 < tiles)
        def _():
            fetch(j + 1, 1 - slot).start()

        fetch(j, slot).wait()
        rows = buf[slot]                                       # [tk, W]
        # the tile's keys and values of the block's heads, one matmul,
        # rounded as the loop's einsum rounds them
        with jax.named_scope("attn/latent_expand"):
            kvs = jnp.dot(rows[:, :rank], w_ref[...],
                          preferred_element_type=jnp.float32
                          ).astype(rows.dtype)                 # [tk, hb kv]
        # head-major in scratch, so that ONE body serves the block's
        # heads by a leading index (unrolled, the kernel's code and its
        # compile grow with the block and it runs no faster)
        for h in range(hb):
            kv_ref[h] = kvs[:, h * kv:(h + 1) * kv]
        # the rest of the row is k_pe and the row's padding: the
        # queries' padding is zeros, and 0 x the padding must be 0
        rest = rows[:, rank:]
        k_pe = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, rest.shape, 1) < rope,
            rest, jnp.zeros_like(rest))
        pos = j * tk + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[1], tk), 1)
        seen = pos <= pos_ref[...]                             # [L, tk]

        def head(h, _):
            kvh = kv_ref[h]                                    # [tk, kv]
            k = jnp.concatenate([kvh[:, :nope], k_pe], axis=1)
            s = jax.lax.dot_general(
                q_ref[h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, _NEG)
            m_old = m_ref[h]
            m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            # every query sees row 0, so from the first tile on m_new is
            # a real score and a masked row's exp(_NEG - m_new) is 0
            p = jnp.exp(s - m_new)
            l_ref[h] = alpha * l_ref[h] + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p.astype(rows.dtype), kvh[:, nope:],
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

        jax.lax.fori_loop(0, hb, head, None)

    jax.lax.fori_loop(0, tiles, tile, None)
    v = kv - nope
    for h in range(hb):
        l = l_ref[h]
        o_ref[:, h * v:(h + 1) * v] = (
            acc_ref[h] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def latent_expand_tiled(q, latent, w_kvb, q_pos, limit, *, rank: int,
                        nope: int, scale: float, interpret=None):
    """:func:`expanded_attention` as one Pallas call (the same
    arguments, the same contract): the grid over lanes and blocks of
    heads (:func:`expand_heads`), a block's queries and its slice of
    ``W_kvb`` resident while a loop inside the kernel walks the tiles of
    rows (:func:`expand_block`) up to ``limit``, scalar-prefetched, and
    no further: each tile is copied as it lies in the cache (two
    buffers: the next tile's copy under this one's matmuls), expanded on
    the MXU for the block's heads, and met by ALL ``L`` queries; the
    expanded rows and the float32 scores never leave VMEM."""
    B, L, H, _ = q.shape
    kv = w_kvb.shape[-1]
    tk = expand_block(B, L, H, kv, latent.shape[1], latent.dtype, True)
    return _expand_call(q, latent, w_kvb, q_pos, limit, rank=rank, nope=nope,
                        scale=scale, tk=tk, hb=expand_heads(H, kv, tk, L),
                        interpret=_interpret(interpret))


# jitted, so that a program's latent layers share ONE trace and ONE
# Mosaic lowering of the kernel: un-jitted, five layers x thirty
# programs traced and lowered it 150 times a process, a minute of every
# warm set-up (PERF.md section 6, PR 42)
@functools.partial(jax.jit, static_argnames=(
    "rank", "nope", "scale", "tk", "hb", "interpret"))
def _expand_call(q, latent, w_kvb, q_pos, limit, *, rank: int, nope: int,
                 scale: float, tk: int, hb: int, interpret: bool):
    B, L, H, Dq = q.shape
    T, W = latent.shape[1:]
    kv = w_kvb.shape[-1]
    rope, dtype = Dq - nope, latent.dtype
    assert T % tk == 0 and H % hb == 0 and W >= rank + rope, (T, tk, H, hb, W)
    # head-major queries, zeros past q_pe up to the rest of a row
    qh = jnp.pad(jnp.moveaxis(q, 2, 1).astype(dtype),
                 ((0, 0), (0, 0), (0, 0), (0, W - rank - rope)))
    w = w_kvb.astype(dtype).reshape(rank, H * kv)
    lim = jnp.asarray(limit, jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_expand_kernel, tk=tk, hb=hb, rank=rank,
                          nope=nope, rope=rope, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H // hb),
            in_specs=[
                pl.BlockSpec((None, hb, L, qh.shape[-1]),
                             lambda b, g, n: (b, g, 0, 0)),
                pl.BlockSpec((None, L, 1), lambda b, g, n: (b, 0, 0)),
                pl.BlockSpec((rank, hb * kv), lambda b, g, n: (0, g)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, L, hb * (kv - nope)),
                                   lambda b, g, n: (b, 0, g)),
            scratch_shapes=[pltpu.VMEM((2, tk, W), dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((hb, L, 1), jnp.float32),
                            pltpu.VMEM((hb, L, 1), jnp.float32),
                            pltpu.VMEM((hb, L, kv - nope), jnp.float32),
                            pltpu.VMEM((hb, tk, kv), dtype)]),
        out_shape=jax.ShapeDtypeStruct((B, L, H * (kv - nope)), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="latent_expand_tiled",
    )(lim, qh, q_pos.astype(jnp.int32)[..., None], w, latent)
    return out.reshape(B, L, H, kv - nope)


# -- absorbed ---------------------------------------------------------------

def absorb(q, w_kvb, *, nope: int, width: int):
    """``q [B, H, nope + rope]`` -> the query against latent rows, ``[B,
    H, width]``: ``W_K[:, h] q_nope_h | q_pe_h | 0``."""
    with jax.named_scope("attn/latent_absorb"):
        q_lat = jnp.einsum("bhd,chd->bhc", q[..., :nope],
                           w_kvb[..., :nope].astype(q.dtype))
    full = jnp.concatenate([q_lat, q[..., nope:]], axis=-1)
    return jnp.pad(full, ((0, 0), (0, 0), (0, width - full.shape[-1])))


def unabsorb(o_lat, w_kvb, *, rank: int, nope: int):
    """``o_lat [B, H, >= rank]`` (the probabilities' sum of latent rows)
    -> ``[B, H, v]``: ``W_V[:, h]^T`` of its first ``rank`` values."""
    with jax.named_scope("attn/latent_absorb"):
        return jnp.einsum("bhc,chd->bhd", o_lat[..., :rank],
                          w_kvb[..., nope:].astype(o_lat.dtype))


def latent_attend_reference(q_lat, latent, lengths, *, scale: float):
    """One absorbed query a head, ``q_lat [B, H, W]``, against the first
    ``lengths[b]`` rows of ``latent [B, T, W]``: ``[B, H, W]`` in
    ``q_lat``'s dtype, the probabilities' sum of the rows.  A slot of
    length 0 returns zeros.  Reads every slot's slab whole."""
    T = latent.shape[1]
    s = jnp.einsum("bhw,btw->bht", q_lat, latent).astype(jnp.float32) * scale
    seen = jnp.arange(T)[None, :] < lengths[:, None]
    s = jnp.where(seen[:, None], s, _NEG)
    p = jnp.where(seen[:, None], jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    l = p.sum(-1, keepdims=True)
    acc = jnp.einsum("bht,btw->bhw", p.astype(latent.dtype), latent
                     ).astype(jnp.float32)
    return (acc / jnp.where(l > 0, l, 1.0)).astype(q_lat.dtype)


def latent_append_reference(latent, row, index, live):
    """``row [B, W]`` at position ``index[b]`` of slot b's rows; slots
    that are free, or at or past the slab's end, keep theirs."""
    B, T, _ = latent.shape
    on = live & (index < T)
    at = jnp.clip(index, 0, T - 1)
    old = latent[jnp.arange(B), at]
    return latent.at[jnp.arange(B), at].set(
        jnp.where(on[:, None], row.astype(latent.dtype), old))


# -- the kernels ------------------------------------------------------------

def _append_kernel(idx_ref, on_ref, lat_ref, new_ref, out_ref, *, rows: int):
    b = pl.program_id(0)
    at, on = idx_ref[b], on_ref[b] != 0
    row = jax.lax.broadcasted_iota(jnp.int32, lat_ref.shape, 0)
    out_ref[...] = jnp.where((row == at % rows) & on,
                             jnp.broadcast_to(new_ref[...], lat_ref.shape),
                             lat_ref[...])


def latent_append(latent, row, index, live, *, interpret=None):
    """:func:`latent_append_reference` as one Pallas call, in place
    (donate or carry ``latent``: it is aliased in and out): one sublane
    tile a slot, chosen by the scalar-prefetched index."""
    B, T, W = latent.shape
    rows = _sublanes(latent.dtype)
    on = (live & (index < T)).astype(jnp.int32)
    at = jnp.clip(index, 0, T - 1).astype(jnp.int32)
    spec = pl.BlockSpec((None, rows, W), lambda b, at, on: (b, at[b] // rows,
                                                            0))
    return pl.pallas_call(
        functools.partial(_append_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[spec, pl.BlockSpec((None, 1, W),
                                         lambda b, at, on: (b, 0, 0))],
            out_specs=spec),
        out_shape=jax.ShapeDtypeStruct(latent.shape, latent.dtype),
        # operands count the two prefetched scalars
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(interpret),
        name="latent_append",
    )(at, on, latent, row.astype(latent.dtype)[:, None, :])


def _attend_kernel(len_ref, src_ref, lo_ref, hi_ref, q_ref, lat_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, tk: int, scale: float):
    b, j = pl.program_id(0), pl.program_id(1)
    n = len_ref[b]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j * tk < n)
    def _():
        q, lat = q_ref[...], lat_ref[...]
        # [Hp, W] x [tk, W]^T -> [Hp, tk], f32: the tile serves every head
        s = jax.lax.dot_general(
            q, lat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        pos = j * tk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < n, s, _NEG)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        # a tile that runs holds a live position, so m_new is a real
        # score and the masked tail's exp(_NEG - m_new) is exactly 0
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        # [Hp, tk] x [tk, W] -> [Hp, W]: the same tile as the values
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(lat.dtype), lat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_ref[...]      # 0 for a free slot, whose acc is 0 too
        o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


def attend_block(W: int, max_len: int, dtype) -> int:
    """Positions a grid step of the attend kernel reads: the largest
    power-of-two multiple of 128 that divides ``max_len`` and keeps one
    tile of rows within ``_BLOCK_BYTES``."""
    tk = _LANES
    while (max_len % (2 * tk) == 0
           and 2 * tk * W * jnp.dtype(dtype).itemsize <= _BLOCK_BYTES):
        tk *= 2
    return tk


def latent_attend(q_lat, latent, lengths, *, scale: float,
                  block: int | None = None, interpret=None):
    """:func:`latent_attend_reference` as one Pallas call: a slot's rows
    are read up to its length, in tiles, each tile once; a slot of
    length 0 reads nothing and returns zeros."""
    B, H, W = q_lat.shape
    T = latent.shape[1]
    tk = block or attend_block(W, T, latent.dtype)
    assert T % tk == 0, (T, tk)
    # the head axis is the matmuls' row axis: pad it to a sublane tile
    Hp = -(-H // _sublanes(q_lat.dtype)) * _sublanes(q_lat.dtype)
    qp = jnp.pad(q_lat, ((0, 0), (0, Hp - H), (0, 0)))
    lengths = lengths.astype(jnp.int32)
    src, lo, hi = _fetch_plan(lengths, tk)

    def lat_index(b, j, n, src, lo, hi):
        return src[b], jnp.clip(j, lo[b], hi[b]), 0

    q_spec = pl.BlockSpec((None, Hp, W), lambda b, *_: (b, 0, 0))
    out = pl.pallas_call(
        functools.partial(_attend_kernel, tk=tk, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B, T // tk),
            in_specs=[q_spec, pl.BlockSpec((None, tk, W), lat_index)],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((Hp, 1), jnp.float32),
                            pltpu.VMEM((Hp, 1), jnp.float32),
                            pltpu.VMEM((Hp, W), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hp, W), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(interpret),
        name="latent_attend",
    )(lengths, src, lo, hi, qp, latent)
    return out[:, :H]


def tokens_fetched(lengths, W: int, max_len: int, dtype, kernel: bool):
    """Positions one attend call fetches of the slots' rows, float32: on
    the kernels' path whole tiles up to each live slot's length (COUNTED
    from the fetch plan: the changes of tile along the grid, as
    ``ops/ssm.slots_fetched`` counts blocks), on the einsum path every
    slot's slab."""
    if not kernel:
        return jnp.asarray(lengths.shape[0] * max_len, jnp.float32)
    tk = attend_block(W, max_len, dtype)
    plan = _fetch_plan(lengths.astype(jnp.int32), tk)
    return decode_attention.blocks_fetched(*plan, max_len // tk) * tk
