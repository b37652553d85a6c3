"""Mixture-of-experts MLP: top-k routed expert FFN, two routed paths.

Beyond-parity capability (the reference's only sparse structure is the
CTR embedding table, example/ctr/).  The router is a float32 softmax
over ``E`` experts; each token takes its ``top_k`` largest
probabilities as gates (renormalised to sum 1 when ``norm_topk``, left
as they are when not: OLMoE's ``norm_topk_prob: false``).  Experts are
``silu(x @ w_in) @ w_out`` or, with ``gated``, the gated form
``(silu(x @ w_gate) * (x @ w_in)) @ w_out``.

Which path runs when:

- **Dropless** (``capacity_factor <= 0``): one path for training,
  prefill, chunked prefill and decode.  The ``B*S*K`` (token, expert)
  assignments are sorted by expert (stable), the token rows gathered in
  that order, the experts run over the sorted rows, and the rows are
  then unsorted, weighted by their gates and summed per token.  Nothing
  is ever dropped; ``token_mask``-ed (pad) positions route nowhere:
  zero weight, in no group, in no count.  No ``[B, S, E, C]`` tensor
  and no per-token weight gather exists on this path.  Serving an
  expert model through it (``serving/engine.py``) no longer drops:
  ``moe_prefill_drops`` reads 0.  What runs the experts over the sorted
  rows:

  - :func:`ragged_experts`, everywhere but the case below: each
    projection is ONE grouped matmul (``jax.lax.ragged_dot``, group
    sizes from a per-expert count: on TPU a Mosaic grouped-matmul
    kernel XLA tiles for us, work listed per (expert, row tile)).  It
    is also the kernel's parity reference.
  - :func:`decode_gmm`, a **decode-shaped call**: one token a slot
    (``S == 1`` with ``decode``), on a TPU, with no mesh, the sorted
    rows small enough for VMEM: what :func:`applies` tests, from what
    the call can observe and nothing else.  The three projections are
    ONE Pallas kernel (``moe_decode_gmm``): a loop walks the touched
    experts alone, each one's matrices stream from HBM once, in
    contiguous chunks of a few MiB (:func:`gmm_chunks`: from the bytes,
    whatever the widths), the next chunk arriving while this one is
    multiplied, an untouched expert is never named, and the hidden rows
    never leave VMEM.  Same mathematics and precision: operands in the
    layer's dtype, float32 accumulation, the hidden rows rounded once.
    The layer sows ``moe_fetched``, the expert weight sets the kernel
    counted itself fetching, beside ``moe_stats``.  Off a TPU the kernel
    runs in Pallas interpret mode (the tier-1 parity tests); nothing
    selects it there.
  - :func:`prefix_gmm`, a **multi-token ``decode`` call of a layer
    that holds a share of its experts** (``held``: the chunk lane's
    programs, bucketed and reuse prefill, the speculative verify), on a
    TPU, with no mesh: what :func:`prefix_rows` tests, from the call's
    shape.  Of the ``B*S*K`` sorted pairs only those on held experts
    are live, about ``held / E`` of them, and they are the FRONT of the
    sorted order.  ``ragged_dot`` lists its work per (expert, row tile)
    and multiplies each tile whole, so its time follows the rows handed
    in, not the live ones (16 experts x one 512-row tile for 256 live
    rows: PERF.md section 6, PR 43).  This path gathers, runs and
    unsorts the first ``Rb`` sorted pairs alone, through the same
    kernel under the name ``moe_prefix_gmm`` (rows in and out held once
    in VMEM); ``Rb`` is the most rows that fit there, and the path is
    taken only where that is 1.25 times the live rows a uniform router
    gives.  A call whose live pairs outgrow ``Rb`` runs the whole-rows
    ``ragged_dot`` path instead, chosen on the device by a
    ``jax.lax.cond`` on the count: nothing is ever dropped.  The layer
    sows ``moe_prefix``, 1 where the call took the prefix and 0 where
    it fell back.
- **Capacity** (``capacity_factor > 0``, the default 1.25): the
  GShard-style path designed for the compiler rather than
  hand-scheduled all-to-alls - routing is expressed as dense
  dispatch/combine einsums against expert weights whose leading axis
  carries the ``expert`` logical name (mapped to ``ep`` by the default
  sharding rules), so XLA derives the token shuffle collectives from
  the shardings the same way it derives the data-parallel gradient
  reduction.  Tokens ``[B, S, M]``, per-expert capacity ``C =
  ceil(top_k * S * capacity_factor / E)`` per batch row; assignments
  past an expert's capacity are dropped (their combine weight is zero -
  the standard GShard/Switch overflow rule), so every tensor is
  static-shaped.  With ``decode=True`` its single-token steps (S <= 2)
  gather each token's experts instead (no capacity, no drops).  This is
  what training over an ``ep`` mesh uses today; its dispatch tensors
  are ``[B, S, E, C]``, which no serving-sized prefill can hold.

The auxiliary load-balance loss is the Switch-Transformer form
``E * sum_e f_e * P_e`` (fraction of tokens top-1-routed to e x mean
router probability of e); minimised at uniform routing.  Both paths
return it (the dropless one only outside ``decode``).

The dropless path also runs the sigmoid-routed family (``router=
"sigmoid"``): float32 sigmoid scores, the ``top_k`` chosen by score
plus a learned per-expert ``gate_bias`` (``select_bias``: the bias
chooses, the score weighs), gates normalised over the chosen and
multiplied by ``routed_scale``; a **shared expert** of width
``shared_dim`` that every token passes through beside the routed ones
(scope ``moe/shared``); and **held experts** (``held`` > 0), one
device's share of expert parallelism without its exchange: the router
is ``num_experts`` wide and every gate is normalised over all the
chosen, but the device holds the matrices of experts ``0 .. held-1``
and computes the (token, expert) pairs that land on them; the others
belong to devices that are not here, and the layer's output is this
device's partial sum.  ``moe_stats`` then carries a fourth entry, the
pairs ROUTED, beside the pairs computed.

What the paths sow into the ``intermediates`` collection (a no-op
unless the caller asks for it): ``moe_drops`` (capacity path, int32)
and ``moe_stats`` (dropless path, float32 ``[assignments, experts
touched, max load over mean load]`` of this layer's call over real
tokens, with ``held`` also ``pairs routed``: ``assignments`` are then
the pairs that landed on held experts, and max load over mean load is
over the held experts), and where the decode kernel ran
``moe_fetched`` (float32 scalar: the expert weight sets it counted
itself fetching), and where a multi-token call may take the prefix
kernel ``moe_prefix`` (float32 scalar: 1 where it did) - the serving
engine sums all four into ``stats()``.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops.attention import _on_tpu
from edl_tpu.ops.decode_attention import _interpret, _sublanes

# rows one pass of the decode kernel's matmuls may take: a group runs
# in the smallest window that holds it (a matmul of few rows costs the
# MXU a weight tile's load plus the rows), the last one looped for a
# group that outgrows it; multiples of every dtype's sublane tile
_WINDOWS = (16, 32, 128)
# bytes of one weight chunk the kernel has in flight (two are in VMEM,
# one computed on, one arriving): small enough that the first chunk's
# fetch and the last one's matmuls, which nothing hides, cost little
_CHUNK_BYTES = 4 << 20
# bytes the sorted rows may take in VMEM (rows in, rows out, both double
# buffered, and the float32 accumulator): a call with more rows than
# that stays on ragged_dot.  The widest served call is a block pass of
# 16 slots x 8 positions x 8 experts: 1,136 rows of 2048 = 26.6 MiB
_ROW_BYTES = 28 << 20
# what the kernel asks of VMEM: those rows (28 MiB at most), two chunks
# a projection (16 MiB at most) and the float32 hidden rows (a quarter
# of the rows' bytes where an expert's two input projections are three
# quarters of the model's width, as in that pass: 7 MiB), so 51 MiB and
# the matmuls' own temporaries
_VMEM_LIMIT = 64 << 20
# what a multi-token call's kernel (moe_prefix_gmm) asks of the chip's
# 128 MiB instead, and what of it the rows may take beside the weight
# chunks and the matmuls' own temporaries: its one grid step has no
# second block to fetch, so rows in and rows out are held once
_PREFIX_VMEM_LIMIT = 96 << 20
_PREFIX_ROW_BYTES = _PREFIX_VMEM_LIMIT - 4 * _CHUNK_BYTES - (8 << 20)
# the prefix must hold this many times the pairs a uniform router would
# land on the held experts (routers seen: up to 1.16x).  The kernel's time
# does not follow the live rows, so only a fallback's rarity is at stake
_PREFIX_MARGIN = 1.25


def compute_routing(probs, top_k: int, capacity: int, valid=None,
                    norm_topk: bool = True):
    """Routing tensors from router probabilities ``[B, S, E]``.

    Returns ``(dispatch [B, S, E, C] in {0,1}, combine [B, S, E, C]
    f32, aux_loss scalar, drops scalar i32)``.  Slot priority is
    k-major (every token's first choice is placed before any token's
    second choice), positions within an expert are sequence-ordered —
    deterministic, no RNG.  ``drops`` counts (token, expert)
    assignments that overflowed capacity — the silent-quality-loss
    signal a serving path must be able to observe.

    ``valid`` ([B, S] bool, optional) marks real tokens: invalid
    positions route NOWHERE — they claim no capacity slot, contribute
    zero combine weight, and are excluded from the drop count and the
    aux loss.  Serving prefill pads prompts to a bucket length; without
    the mask, pad tokens consume capacity ahead of real tokens' lower
    choices and the padded forward diverges from generate() on the
    same prompt.
    """
    B, S, E = probs.shape
    gates, idx = top_k_gates(probs, top_k, norm_topk)     # [B, S, K]
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)    # [B, S, K, E]
    if valid is not None:
        onehot = onehot * valid[:, :, None, None].astype(jnp.float32)

    # k-major slot order: [B, K*S, E]
    slots = onehot.transpose(0, 2, 1, 3).reshape(B, top_k * S, E)
    pos = (jnp.cumsum(slots, axis=1) * slots).astype(jnp.int32) - 1
    kept = (pos >= 0) & (pos < capacity)
    total = (jnp.asarray(B * S, jnp.int32) if valid is None
             else valid.sum().astype(jnp.int32)) * top_k
    drops = total - kept.sum().astype(jnp.int32)          # overflowed slots
    pos_c = jax.nn.one_hot(pos, capacity, dtype=jnp.float32) * kept[..., None]
    # back to token-major [B, S, K, E, C]; merge k (distinct (e, c) each)
    pos_c = pos_c.reshape(B, top_k, S, E, capacity).transpose(0, 2, 1, 3, 4)
    dispatch = pos_c.sum(axis=2)                          # [B, S, E, C]
    combine = jnp.einsum("bske,bskec->bsec",
                         onehot * gates[..., None], pos_c)

    return dispatch, combine, switch_aux_loss(probs, idx, valid), drops


def top_k_gates(probs, top_k: int, norm_topk: bool):
    """``(gates, idx)`` [B, S, K]: each token's ``top_k`` largest
    router probabilities, renormalised to sum 1 when ``norm_topk``."""
    gates, idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx


def sigmoid_gates(scores, bias, top_k: int, norm_topk: bool,
                  scale: float = 1.0):
    """``(gates, idx)`` [B, S, K] of a sigmoid router: the ``top_k``
    experts by ``scores + bias`` (``bias`` [E] or None), weighed by
    their ``scores`` alone, normalised over the chosen when
    ``norm_topk``, times ``scale``."""
    _, idx = jax.lax.top_k(scores if bias is None else scores + bias, top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-20)
    return gates * scale, idx


def switch_aux_loss(probs, idx, valid=None):
    """Switch aux loss from top-1 assignments (over real tokens only)."""
    E = probs.shape[-1]
    top1 = jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32)
    if valid is None:
        frac_tokens = top1.mean(axis=(0, 1))              # [E]
        frac_prob = probs.mean(axis=(0, 1))               # [E]
    else:
        v = valid.astype(jnp.float32)[..., None]
        n = jnp.maximum(v.sum(), 1.0)
        frac_tokens = (top1 * v).sum(axis=(0, 1)) / n
        frac_prob = (probs * v).sum(axis=(0, 1)) / n
    return E * jnp.sum(frac_tokens * frac_prob)


def _activate(h, gate=None):
    """An expert's hidden activation: ``silu(h)``, or the gated form
    ``silu(gate) * h`` (``gate`` the x @ w_gate projection)."""
    return nn.silu(h) if gate is None else nn.silu(gate) * h


def ragged_experts(rows, sizes, w_gate, w_in, w_out):
    """The routed experts over rows sorted by expert, ``rows [R, M]``
    with ``sizes [E]`` rows a group: one ``jax.lax.ragged_dot`` a
    projection.  Every path's expert FFN but the decode step's on a
    TPU, and :func:`decode_gmm`'s parity reference.  What it leaves in
    rows past the last group is unspecified."""
    h = _activate(
        jax.lax.ragged_dot(rows, w_in, sizes),
        None if w_gate is None else jax.lax.ragged_dot(rows, w_gate, sizes))
    return jax.lax.ragged_dot(h, w_out, sizes)


def applies(S: int, mesh, rows: int, M: int, dtype) -> bool:
    """Whether a dropless call takes the decode kernel: one token a
    slot on a TPU with no mesh, the sorted rows small enough to stay in
    VMEM.  Training, a mesh engine and every other backend keep
    ``ragged_dot``; so do prefill, the chunk lane and the speculative
    verify but where :func:`prefix_rows` gives them a bound."""
    return (S == 1 and mesh is None and _gmm_row_bytes(rows, M, dtype)
            <= _ROW_BYTES and _on_tpu())


def prefix_rows(T: int, K: int, held: int, E: int, M: int, H: int, dtype,
                gated: bool = True, mesh=None, decode: bool = True):
    """The rows ``Rb`` a multi-token ``decode`` call of ``T`` tokens
    hands the kernel, or None where it stays on ``ragged_dot`` whole.
    A layer that holds ``held`` of its ``E`` experts owns about ``T * K
    * held / E`` of the ``T * K`` sorted pairs, all at their front: the
    kernel runs over the first ``Rb`` of them, every row where they fit
    VMEM (:data:`_PREFIX_ROW_BYTES`: rows in and out once, the float32
    result and hidden rows), else the largest sublane-tile multiple that
    does.  None where that is under :data:`_PREFIX_MARGIN` times the
    expected live rows (no share held: every row is live), with a mesh,
    off a TPU and outside ``decode`` (training differentiates the layer;
    the kernel has no gradient).  From the call's shape alone."""
    if not (held and decode and mesh is None and _on_tpu()):
        return None
    tile = _sublanes(dtype)
    row = M * (2 * jnp.dtype(dtype).itemsize + 4) + (2 if gated else 1) * H * 4
    fit = _PREFIX_ROW_BYTES // row               # rows as _gmm_rows pads them
    Rb = T * K if _gmm_rows(T * K, dtype) <= fit else (
        fit - _WINDOWS[-1]) // tile * tile + tile
    return Rb if Rb >= _PREFIX_MARGIN * T * K * held / E else None


def _gmm_rows(rows: int, dtype) -> int:
    """Rows the kernel's row blocks hold: windows start at sublane
    tiles, so the widest may reach its width past the tile the last row
    lies in."""
    tile = _sublanes(dtype)
    return (rows - 1) // tile * tile + _WINDOWS[-1]


def _gmm_row_bytes(rows: int, M: int, dtype) -> int:
    return _gmm_rows(rows, dtype) * M * (4 * jnp.dtype(dtype).itemsize + 4)


def _chunk_rows(n: int, row_bytes: int) -> int:
    """Rows of a weight matrix one chunk holds: the largest lane-tile
    multiple that divides ``n`` and fits ``_CHUNK_BYTES``, the smallest
    where none fits, all ``n`` where no lane tile divides it."""
    tiles = [t for t in range(n, 0, -128) if n % t == 0] if n % 128 == 0 \
        else [n]
    return next((t for t in tiles if t * row_bytes <= _CHUNK_BYTES),
                tiles[-1])


def gmm_chunks(M: int, H: int, gated: bool, dtype) -> tuple[int, int]:
    """``(tm, th)``: the kernel streams an expert as ``M // tm`` chunks
    of its input projections (rows ``[tm, H]`` of ``w_in`` and of
    ``w_gate``, contiguous) and then ``H // th`` chunks of ``w_out``
    (rows ``[th, M]``): whatever the widths, from the bytes alone."""
    item = jnp.dtype(dtype).itemsize
    return (_chunk_rows(M, (2 if gated else 1) * H * item),
            _chunk_rows(H, M * item))


def _gmm_plan(sizes):
    """What the kernel walks: the touched experts in order (then the
    others, never read), each expert's rows and their offset among the
    sorted rows, and how many are touched."""
    sizes = sizes.astype(jnp.int32)
    ids = jnp.argsort(sizes == 0, stable=True).astype(jnp.int32)
    return (ids, sizes, jnp.cumsum(sizes) - sizes,
            jnp.sum(sizes > 0, dtype=jnp.int32).reshape(1))


def _gmm_kernel(ids_ref, sizes_ref, offs_ref, nt_ref, x_ref, *refs,
                gated: bool):
    """The whole call: the sorted rows in VMEM (``x_ref [na, Rp, tm]``,
    a chunk's columns at a time), the stacked weights in HBM.  A loop
    over the touched experts streams each one's chunks
    (:func:`gmm_chunks`) through two buffers a projection, the next
    chunk arriving while this one is multiplied: the ``na`` input chunks
    accumulate the float32 hidden rows ``h_ref [G, nb, Rp, th]`` (gate
    and up), the ``nb`` output chunks the float32 result ``acc_ref [Rp,
    M]``.  ``cnt_ref`` counts the chunk fetches started."""
    G = 2 if gated else 1
    ins, (wo_hbm, o_ref, cnt_ref, a_buf, b_buf, a_sem, b_sem, h_ref,
          acc_ref) = refs[:G], refs[G:]
    na, _, tm = x_ref.shape
    nb, _, th = h_ref.shape[1:]
    tile = _sublanes(x_ref.dtype)
    f32 = jnp.float32
    nt = nt_ref[0]

    def a_copies(e, k):
        rows = pl.ds(pl.multiple_of(k * tm, tm), tm)
        return [pltpu.make_async_copy(w.at[e, rows, :], a_buf.at[k % 2, g],
                                      a_sem.at[k % 2, g])
                for g, w in enumerate(ins)]

    def b_copies(e, j):
        rows = pl.ds(pl.multiple_of(j * th, th), th)
        return [pltpu.make_async_copy(wo_hbm.at[e, rows, :], b_buf.at[j % 2],
                                      b_sem.at[j % 2])]

    def start(copies):
        for c in copies:
            c.start()
        cnt_ref[0] += 1

    cnt_ref[0] = 0
    h_ref[...] = jnp.zeros(h_ref.shape, f32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    @pl.when(nt > 0)
    def _():
        start(a_copies(ids_ref[0], 0))

    def expert(t, carry):
        e = ids_ref[t]
        n, off = sizes_ref[e], offs_ref[e]
        first = off // tile * tile
        span = off - first + n

        def windows(multiply):
            """``multiply(here, keep)`` over the group's rows: one pass
            in the smallest window that holds them, the widest looped
            where none does.  A window is sublane aligned, the group is
            not: rows of its neighbours are computed with it and not
            kept."""
            def one(W, w):
                at = pl.multiple_of(first + w * W, tile)
                r = at + jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
                multiply(pl.ds(at, W), (r >= off) & (r < off + n))

            for fits, W in zip((0,) + _WINDOWS, _WINDOWS[:-1]):
                pl.when((span > fits) & (span <= W))(
                    functools.partial(one, W, 0))
            W = _WINDOWS[-1]

            @pl.when(span > _WINDOWS[-2])
            def _():
                jax.lax.fori_loop(0, pl.cdiv(span, W),
                                  lambda w, c: one(W, w) or c, 0)

        def a_chunk(k, carry):
            @pl.when(k + 1 < na)
            def _():
                start(a_copies(e, k + 1))

            @pl.when(k + 1 == na)
            def _():
                start(b_copies(e, 0))

            for c in a_copies(e, k):
                c.wait()

            def multiply(here, keep):
                rows = x_ref[k, here, :]
                for g in range(G):
                    h = jnp.where(keep, jnp.dot(
                        rows, a_buf[k % 2, g], preferred_element_type=f32),
                        0.0)
                    for j in range(nb):
                        h_ref[g, j, here, :] += h[:, j * th:(j + 1) * th]

            windows(multiply)
            return carry

        def b_chunk(j, carry):
            @pl.when(j + 1 < nb)
            def _():
                start(b_copies(e, j + 1))

            @pl.when((j + 1 == nb) & (t + 1 < nt))
            def _():
                start(a_copies(ids_ref[t + 1], 0))

            for c in b_copies(e, j):
                c.wait()

            def multiply(here, keep):
                h = _activate(h_ref[G - 1, j, here, :],
                              h_ref[0, j, here, :] if gated else None)
                acc_ref[here, :] += jnp.where(keep, jnp.dot(
                    h.astype(x_ref.dtype), b_buf[j % 2],
                    preferred_element_type=f32), 0.0)

            windows(multiply)
            return carry

        jax.lax.fori_loop(0, na, a_chunk, 0)
        jax.lax.fori_loop(0, nb, b_chunk, 0)
        return carry

    jax.lax.fori_loop(0, nt, expert, 0)
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def decode_gmm(rows, sizes, w_gate, w_in, w_out, *, interpret=None):
    """:func:`ragged_experts` as ONE Pallas call (``moe_decode_gmm``),
    for the few rows an expert a decode step has, and beside its output
    the expert weight sets it fetched (float32: the chunk fetches the
    kernel counted as it started them, over the chunks an expert has).
    The kernel walks the touched experts alone; each one's matrices
    stream from HBM once, in contiguous chunks of :func:`gmm_chunks`,
    the next chunk (the next expert's first after this one's last)
    arriving while this one is multiplied; the hidden rows stay in VMEM,
    float32 until their one rounding to the operands' dtype before the
    down projection.  Rows past the last group come back zero."""
    M, H = w_in.shape[1:]
    return _decode_gmm(
        rows, sizes, w_gate, w_in, w_out, _interpret(interpret),
        *gmm_chunks(M, H, w_gate is not None, rows.dtype))


def prefix_gmm(rows, sizes, w_gate, w_in, w_out, *, bound=None,
               interpret=None):
    """:func:`decode_gmm` for a multi-token call's rows, as the kernel
    ``moe_prefix_gmm``: the same walk over the touched experts, the
    same chunks and windows, the same arithmetic; the rows in and the
    rows out are whole in VMEM, once each, where the decode step's
    blocks are the pipeline's two.  ``bound`` (:func:`prefix_rows`; the
    rows handed in where None): no group reaches past it.  A window may
    (:func:`_gmm_rows`), so the caller hands in the rows up to there
    where it has them (they cost a gather, a pad costs a copy) and the
    result comes back that long, ``[_gmm_rows(bound), M]``, zero past
    the last group."""
    M, H = w_in.shape[1:]
    return _decode_gmm(
        rows, sizes, w_gate, w_in, w_out, _interpret(interpret),
        *gmm_chunks(M, H, w_gate is not None, rows.dtype),
        rows.shape[0] if bound is None else bound)


# a program's layers are alike: jitted, the kernel is traced and lowered
# once a program, not once a layer
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _decode_gmm(rows, sizes, w_gate, w_in, w_out, interpret: bool, tm: int,
                th: int, prefix: int = 0):
    # prefix: 0 = the decode step's plan; else prefix_gmm's bound
    R, M = rows.shape
    H = w_in.shape[2]
    G = 1 if w_gate is None else 2
    na, nb = M // tm, H // th
    Rp = _gmm_rows(prefix or R, rows.dtype)
    # a chunk's columns of every row together: [na, Rp, tm]
    x = jnp.pad(rows, ((0, Rp - R), (0, 0))).reshape(Rp, na, tm).swapaxes(
        0, 1)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    out, count = pl.pallas_call(
        functools.partial(_gmm_kernel, gated=G == 2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(1,),
            in_specs=[whole if prefix else pl.BlockSpec(
                (na, Rp, tm), lambda *_: (0, 0, 0))] + [hbm] * (G + 1),
            out_specs=[whole if prefix else pl.BlockSpec(
                (Rp, M), lambda *_: (0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[
                pltpu.VMEM((2, G, tm, H), rows.dtype),
                pltpu.VMEM((2, th, M), rows.dtype),
                pltpu.SemaphoreType.DMA((2, G)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((G, nb, Rp, th), jnp.float32),
                pltpu.VMEM((Rp, M), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((Rp, M), rows.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_PREFIX_VMEM_LIMIT if prefix else _VMEM_LIMIT),
        interpret=interpret,
        name="moe_prefix_gmm" if prefix else "moe_decode_gmm",
    )(*_gmm_plan(sizes), x, *([] if w_gate is None else [w_gate]), w_in,
      w_out)
    return (out if prefix else out[:R],
            count[0].astype(jnp.float32) / (na + nb))


def dropless_experts(x, gates, idx, w_gate, w_in, w_out, valid=None,
                     held_only: bool = False, kernel: bool = False,
                     prefix=None):
    """The dropless routed expert FFN over tokens ``x [T, M]`` with
    gates and expert indices ``[T, K]``: sort the ``T*K`` assignments
    by expert, the experts over the sorted rows, unsort, weight and sum
    per token.  The experts are one grouped matmul per projection
    (:func:`ragged_experts`) or, with ``kernel`` (a decode-shaped call
    where :func:`applies` says so), the three projections as one Pallas
    call (:func:`decode_gmm`); sort, unsort and weighting are the same
    XLA ops around either.  ``w_gate`` None = ungated.
    ``valid [T]`` bool marks real tokens; the others are sorted past
    every group (sentinel expert ``E``), so no expert computes them.
    So is a pair whose expert index is ``E`` or more: an expert this
    device does not hold (``MoEMLP.held``); its gate weighs nothing
    here.

    ``prefix`` (:func:`prefix_rows`, a multi-token call of a layer that
    holds a share of its experts): the live pairs are the FRONT of the
    sorted ones, so only the first ``prefix`` are gathered, run
    (:func:`prefix_gmm`) and unsorted; a pair past them reads zero, as
    its gate does.  A call whose live pairs outgrow ``prefix`` takes
    the whole-rows ``ragged_dot`` path instead, chosen on the device
    (``jax.lax.cond``): nothing is dropped either way.

    Returns ``(y [T, M] in x's dtype, sizes [E] int32, fetched,
    took_prefix)`` - ``sizes`` the real assignments each expert
    received, ``fetched`` the expert weight sets the decode kernel
    counted itself fetching (None on the other paths: ``ragged_dot``'s
    reads are not the program's to count), ``took_prefix`` float32 1
    where a call with ``prefix`` ran over it and 0 where it fell back
    (None without ``prefix``)."""
    if prefix is not None:
        # a program's layers are alike, and this path carries both
        # branches: traced and lowered once a program
        return _prefix_experts(x, gates, idx, w_gate, w_in, w_out, valid,
                               held_only=held_only, prefix=prefix)
    return _dropless_experts(x, gates, idx, w_gate, w_in, w_out, valid,
                             held_only, kernel)


def _dropless_experts(x, gates, idx, w_gate, w_in, w_out, valid, held_only,
                      kernel=False, prefix=None):
    T, K = idx.shape
    E = w_in.shape[0]
    with jax.named_scope("moe/route"):
        flat = idx.reshape(T * K).astype(jnp.int32)
        if valid is not None:
            flat = jnp.where(jnp.repeat(valid, K), flat, E)
        order = jnp.argsort(flat, stable=True)            # sorted -> flat
        sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
        # rows past the last group belong to pad tokens: what a grouped
        # matmul leaves there is unspecified, so they are zeroed
        in_group = jnp.arange(T * K) < sizes.sum()

    def weigh(out):
        """The unsorted rows ``[T*K, M]``, each token's K weighted and
        summed in float32."""
        out = out.reshape(T, K, -1).astype(jnp.float32)
        w = gates.astype(jnp.float32)
        if valid is not None:
            w = w * valid[:, None]
        if held_only:
            w = w * (idx < E)
        return (out * w[..., None]).sum(axis=1).astype(x.dtype)

    def whole():
        with jax.named_scope("moe/experts"):
            rows = x[order // K]                          # [T*K, M]
            if kernel:
                out, fetched = decode_gmm(rows, sizes, w_gate, w_in, w_out)
            else:                                         # [T*K, M]
                out, fetched = ragged_experts(rows, sizes, w_gate, w_in,
                                              w_out), None
        with jax.named_scope("moe/combine"):
            out = jnp.where(in_group[:, None], out, 0)
            # unsort with the inverse permutation (a gather, not a
            # scatter-add) and sum each token's K rows in float32
            inverse = jnp.zeros_like(order).at[order].set(jnp.arange(T * K))
            return weigh(out[inverse]), fetched

    def front():
        with jax.named_scope("moe/experts"):
            # the prefix and what a window may reach past it
            reach = min(T * K, _gmm_rows(prefix, x.dtype))
            rows = x[order[:reach] // K]
            # rows past the last group come back zero
            out, _ = prefix_gmm(rows, sizes, w_gate, w_in, w_out,
                                bound=prefix)
        with jax.named_scope("moe/combine"):
            # a pair past the prefix reads zero
            inverse = jnp.zeros_like(order).at[order].set(jnp.arange(T * K))
            return weigh(jnp.take(out, inverse, axis=0, mode="fill",
                                  fill_value=0))

    if prefix is None:
        y, fetched = whole()
        return y, sizes, fetched, None
    if prefix >= T * K:
        return front(), sizes, None, jnp.ones((), jnp.float32)
    fits = sizes.sum() <= prefix
    y = jax.lax.cond(fits, front, lambda: whole()[0])
    return y, sizes, None, fits.astype(jnp.float32)


_prefix_experts = jax.jit(_dropless_experts,
                          static_argnames=("held_only", "prefix"))


class MoEMLP(nn.Module):
    """Top-k routed expert FFN (drop-in for a transformer MLP block).

    Returns ``(y [B, S, M], aux_loss scalar)``.  Expert weights carry
    the ``expert`` leading logical axis; shard them over ``ep`` via the
    default rules (LOGICAL_RULES in models/transformer.py adds the
    matching param-path entries).

    ``capacity_factor <= 0`` selects the dropless path (module
    docstring) for every call: training, prefill, chunked prefill and
    decode run the same sort + grouped matmuls (a ``decode`` call of
    one token a slot runs them as one kernel where :func:`applies`
    says so, a multi-token one of a layer with ``held`` over the live
    prefix of the sorted pairs where :func:`prefix_rows` gives a
    bound), nothing is dropped, and the layer's ``moe_stats`` are sown
    into ``intermediates``.

    ``capacity_factor > 0`` keeps the capacity path.  There
    ``decode=True`` (incremental generation) sends the single-token
    steps (S <= 2) through a per-token expert gather - no capacity
    machinery and therefore no drops - while prefill (decode=True with
    a long S) takes the capacity path and CAN drop on overflow; the
    drop count is sown as ``moe_drops`` so serving paths can surface
    it (pass ``mutable=["cache", "intermediates"]``).  Capacity is
    computed from the STATIC sequence length S, so a bucket-padded
    prefill (serving/engine.py) gets a larger capacity than the same
    prompt unpadded through generate(): with ``token_mask`` the padded
    path can only drop FEWER (never more) real-token assignments."""

    num_experts: int
    mlp_dim: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    decode: bool = False
    gated: bool = False
    norm_topk: bool = True
    # the sigmoid-routed family, a shared expert and held experts
    # (module docstring): dropless path only
    router: str = "softmax"
    select_bias: bool = False
    routed_scale: float = 1.0
    shared_dim: int = 0
    held: int = 0
    # the mesh the caller's arrays are sharded over, if any: what
    # :func:`applies` reads beside the call's shape
    mesh: Any = None
    # positions a slot a decode-shaped call has: 1, or the two blocks of
    # a block pass (``TransformerConfig.pass_tokens``; half of them in a
    # pass of the open block alone), whose ``B x S`` tokens are as few
    # rows an expert as a token step's
    pass_tokens: int = 1

    @nn.compact
    def __call__(self, x, token_mask=None):
        """``token_mask`` ([B, S] bool, optional): real-token mask for
        padded prefill - see :func:`compute_routing`."""
        B, S, M = x.shape
        E = self.num_experts
        plain = (self.router == "softmax" and not self.select_bias
                 and self.routed_scale == 1.0 and not self.shared_dim
                 and not self.held)
        if self.router not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router {self.router!r}")
        if self.select_bias and self.router != "sigmoid":
            raise ValueError("a selection bias belongs to the sigmoid router")
        if not plain and self.capacity_factor > 0:
            raise ValueError(
                "a sigmoid router, a selection bias, a gate scale, a "
                "shared expert and held experts run on the dropless path "
                "only (capacity_factor <= 0): the capacity path's "
                "dispatch tensors know none of them")
        Eh = self.held or E       # experts whose matrices live here
        gate_w = self.param("gate", nn.initializers.lecun_normal(),
                            (M, E), jnp.float32)
        w_in = self.param("w_in", nn.initializers.lecun_normal(),
                          (Eh, M, self.mlp_dim), jnp.float32)
        w_out = self.param("w_out", nn.initializers.lecun_normal(),
                           (Eh, self.mlp_dim, M), jnp.float32)
        w_gate = (self.param("w_gate", nn.initializers.lecun_normal(),
                             (Eh, M, self.mlp_dim), jnp.float32)
                  if self.gated else None)
        bias = (self.param("gate_bias", nn.initializers.zeros, (E,),
                           jnp.float32) if self.select_bias else None)

        # router in f32 (tiny matmul, routing decisions precision-critical)
        scores = x.astype(jnp.float32) @ gate_w
        probs = (jax.nn.softmax(scores, axis=-1) if self.router == "softmax"
                 else jax.nn.sigmoid(scores))
        dtype = self.dtype

        if self.capacity_factor <= 0:
            if self.router == "sigmoid":
                gates, idx = sigmoid_gates(probs, bias, self.top_k,
                                           self.norm_topk, self.routed_scale)
            else:
                gates, idx = top_k_gates(probs, self.top_k, self.norm_topk)
                if self.routed_scale != 1.0:
                    gates = gates * self.routed_scale
            valid = None if token_mask is None else token_mask.reshape(B * S)
            xt = x.reshape(B * S, M).astype(dtype)
            kernel = self.decode and applies(
                1 if self.pass_tokens in (S, 2 * S) else S, self.mesh,
                B * S * self.top_k, M, dtype)
            prefix = None if S == 1 else prefix_rows(
                B * S, self.top_k, self.held, E, M, self.mlp_dim, dtype,
                self.gated, self.mesh, self.decode)
            y, sizes, fetched, took_prefix = dropless_experts(
                xt, gates.reshape(B * S, -1), idx.reshape(B * S, -1),
                None if w_gate is None else w_gate.astype(dtype),
                w_in.astype(dtype), w_out.astype(dtype), valid,
                held_only=bool(self.held), kernel=kernel, prefix=prefix)
            for name, count in (("moe_fetched", fetched),
                                ("moe_prefix", took_prefix)):
                if count is not None:
                    self.sow("intermediates", name, count,
                             init_fn=lambda: jnp.zeros((), jnp.float32),
                             reduce_fn=lambda a, b: a + b)
            total = sizes.sum().astype(jnp.float32)
            stats = [total, (sizes > 0).sum().astype(jnp.float32),
                     sizes.max() * Eh / jnp.maximum(total, 1.0)]
            if self.held:
                real = (jnp.asarray(B * S, jnp.float32) if valid is None
                        else valid.sum().astype(jnp.float32))
                stats.append(real * self.top_k)
            self.sow("intermediates", "moe_stats", jnp.stack(stats),
                     init_fn=lambda: jnp.zeros((len(stats),), jnp.float32),
                     reduce_fn=lambda a, b: a + b)
            if self.shared_dim:
                with jax.named_scope("moe/shared"):
                    dense = dict(use_bias=False, dtype=dtype,
                                 param_dtype=jnp.float32)
                    h = nn.silu(nn.Dense(self.shared_dim, name="shared_gate",
                                         **dense)(xt)) * nn.Dense(
                        self.shared_dim, name="shared_in", **dense)(xt)
                    shared = nn.Dense(M, name="shared_out", **dense)(h)
                    if valid is not None:
                        shared = jnp.where(valid[:, None], shared, 0)
                    y = y + shared.astype(y.dtype)
            aux = (jnp.zeros((), jnp.float32) if self.decode
                   else switch_aux_loss(probs, idx, token_mask))
            return y.reshape(B, S, M), aux

        # per-token gather only for the incremental steps (S <= 2,
        # whatever top_k is): prefill (decode=True, S = prompt) falls
        # through to the capacity path - the training forward's exact
        # semantics
        if self.decode and S <= 2:
            gates, idx = top_k_gates(probs, self.top_k, self.norm_topk)
            xk = x.astype(dtype)
            h = _activate(
                jnp.einsum("bsm,bskmh->bskh", xk, w_in[idx].astype(dtype)),
                None if w_gate is None else jnp.einsum(
                    "bsm,bskmh->bskh", xk, w_gate[idx].astype(dtype)))
            out = jnp.einsum("bskh,bskhm->bskm", h,
                             w_out[idx].astype(dtype))
            y = (out * gates[..., None].astype(dtype)).sum(axis=2)
            # module dtype, not input dtype: the block's norm emits f32
            # (f32 scale param), and a f32 MoE output would promote the
            # residual stream out of bf16 on TPU
            return y.astype(dtype), jnp.zeros((), jnp.float32)

        capacity = max(1, math.ceil(
            self.top_k * S * self.capacity_factor / E))
        dispatch, combine, aux, drops = compute_routing(
            probs, self.top_k, capacity, valid=token_mask,
            norm_topk=self.norm_topk)
        # observable overflow: serving reads this via the intermediates
        # collection (training ignores it at zero cost - sow is a no-op
        # unless the caller asks for the collection)
        self.sow("intermediates", "moe_drops", drops,
                 init_fn=lambda: jnp.zeros((), jnp.int32),
                 reduce_fn=lambda a, b: a + b)

        expert_in = jnp.einsum("bsec,bsm->ebcm", dispatch.astype(dtype),
                               x.astype(dtype))
        h = _activate(
            jnp.einsum("ebcm,emh->ebch", expert_in, w_in.astype(dtype)),
            None if w_gate is None else jnp.einsum(
                "ebcm,emh->ebch", expert_in, w_gate.astype(dtype)))
        out = jnp.einsum("ebch,ehm->ebcm", h, w_out.astype(dtype))
        y = jnp.einsum("bsec,ebcm->bsm", combine.astype(dtype), out)
        return y.astype(dtype), aux
