"""Softmax cross-entropy of a language-model head from the hidden states
and the head's weight, without the ``[N, V]`` logits ever existing whole.

``lm_loss`` of the dense head holds ``[N, V]`` float32 logits and a
``log_softmax`` copy of them (2 x 2.1 GB at 16,384 tokens of a
32,768-word vocabulary).  Two paths here hold one block of them at a
time.  Both run bf16 operands with float32 accumulation, float32
softmax and log-sum-exp on a float32 logits block, and form
``dlogits = softmax - onehot`` in float32 before it is cast to the
compute dtype for the two gradient matmuls.  Which runs is
``models/transformer.lm_loss_fused``'s choice, from where the head lies.

**The token sweep** (:func:`sweep_cross_entropy`, scope ``ce/sweep``):
where the head's weight lies whole on every device.  One loop over
blocks of TOKENS, each against the whole head, so a row's log-sum-exp is
known inside its block and, when the loss is differentiated, the
gradients are formed in the same sweep while the logits are at hand.
Three head-sized matmuls a step (logits, ``dhidden``, ``dW``), the
number the mathematics needs; the backward rule multiplies two stored
arrays by the scalar cotangent, which is why the differentiable function
is the scalar ``sum(token_weight * nll)`` and not the per-token ``nll``.
It holds one block's float32 logits (a budget of 256 MiB fixes the block:
2048 tokens at ``V = 32768``) and its ``dlogits``, a float32 ``[D, V]``
accumulator for ``dW`` and ``dhidden`` in the hidden states' dtype:
O(block x V + D x V + N x D).  On a mesh every device sweeps its own
rows under ``shard_map`` and ``dW`` is summed over the devices once,
after the loop.

**The vocabulary-block loop** (:func:`blockwise_cross_entropy`, scope
``ce/vocab_blocks``): per-token ``nll`` under any cotangent, and the
path of a head that is split (``embed -> fsdp``, ``vocab -> tp``), which
is never whole anywhere: a block of ``block_size`` columns is gathered,
used and dropped.  The forward folds ``[N, block]`` logits tiles into an
online log-sum-exp (flash attention's recurrence on the softmax
denominator); no gradient can be formed before the last block, so the
backward computes every tile of logits a second time from the saved
log-sum-exp: four head-sized matmuls a step (logits twice, ``dhidden``,
``dW``).  It holds one ``[N, block]`` float32 tile, a float32 ``[N, D]``
accumulator for ``dhidden`` and the float32 ``dW`` blocks:
O(N x block + N x D + D x V).

Plain JAX under ``custom_vjp``: both run on the CPU test mesh as on the
chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from edl_tpu.parallel.sharding import logical_sharding

NEG_INF = -1e30  # finite: keeps exp()=0 without inf-inf NaNs
# one token block's float32 logits in the sweep: 2048 tokens at V = 32768
SWEEP_LOGITS_BYTES = 256 << 20


def _pad_blocks(weight, block_size: int):
    """[D, V] -> ([nb, bs, D] stacked blocks, V, nb)."""
    D, V = weight.shape
    nb = -(-V // block_size)
    pad = nb * block_size - V
    wt = weight.T  # [V, D]
    if pad:
        wt = jnp.pad(wt, ((0, pad), (0, 0)))
    return wt.reshape(nb, block_size, D), V, nb


def _block_logits(hidden_f, wb, start, bs, V):
    """f32 [N, bs] logits for one vocab block; padded columns -> -inf."""
    logits = jnp.einsum("nd,bd->nb", hidden_f, wb,
                        preferred_element_type=jnp.float32)
    cols = start + jnp.arange(bs)
    return jnp.where(cols[None, :] < V, logits, NEG_INF)


def _target_logit(logits, idx):
    """The logit at column ``idx`` of every row of a tile (``idx`` clipped
    into the tile: the caller knows which rows' targets lie inside it)."""
    safe = jnp.clip(idx, 0, logits.shape[-1] - 1)
    return jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]


def _softmax_minus_onehot(logits, lse, idx):
    """f32 ``softmax - onehot`` of a tile whose rows' log-sum-exp is
    ``lse``; a row whose target ``idx`` lies outside the tile has no one."""
    cols = jnp.arange(logits.shape[-1])
    return jnp.exp(logits - lse[..., None]) - (cols == idx[..., None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _blockwise_ce(hidden, weight, targets, block_size):
    nll, _ = _ce_fwd_impl(hidden, weight, targets, block_size)
    return nll


def _ce_fwd_impl(hidden, weight, targets, block_size):
    N = hidden.shape[0]
    wblocks, V, nb = _pad_blocks(weight, block_size)
    bs = wblocks.shape[1]
    hidden_f = hidden  # keep bf16 for the MXU; f32 accumulation via pet

    def fold(carry, inp):
        m, l, tgt = carry
        wb, start = inp
        logits = _block_logits(hidden_f, wb, start, bs, V)
        m_new = jnp.maximum(m, logits.max(axis=1))
        l = l * jnp.exp(m - m_new) + jnp.exp(
            logits - m_new[:, None]).sum(axis=1)
        idx = targets - start
        tgt = jnp.where((idx >= 0) & (idx < bs), _target_logit(logits, idx),
                        tgt)
        return (m_new, l, tgt), None

    starts = jnp.arange(nb) * bs
    init = (jnp.full((N,), NEG_INF, jnp.float32),
            jnp.zeros((N,), jnp.float32),
            jnp.full((N,), NEG_INF, jnp.float32))
    with jax.named_scope("ce/vocab_blocks"):
        (m, l, tgt), _ = jax.lax.scan(fold, init, (wblocks, starts))
    lse = m + jnp.log(l)
    return lse - tgt, lse


def _ce_fwd(hidden, weight, targets, block_size):
    nll, lse = _ce_fwd_impl(hidden, weight, targets, block_size)
    return nll, (hidden, weight, targets, lse)


def _ce_bwd(block_size, res, g):
    hidden, weight, targets, lse = res
    N, D = hidden.shape
    wblocks, V, nb = _pad_blocks(weight, block_size)
    bs = wblocks.shape[1]

    def fold(dh, inp):
        wb, start = inp
        logits = _block_logits(hidden, wb, start, bs, V)   # pad -> p = 0
        dlogits = _softmax_minus_onehot(logits, lse, targets - start
                                        ) * g[:, None]     # [N, bs] f32
        dh = dh + jnp.einsum("nb,bd->nd", dlogits, wb,
                             preferred_element_type=jnp.float32)
        dwb = jnp.einsum("nb,nd->bd", dlogits, hidden,
                         preferred_element_type=jnp.float32)
        return dh, dwb

    starts = jnp.arange(nb) * bs
    with jax.named_scope("ce/vocab_blocks"):
        dh, dwbs = jax.lax.scan(fold, jnp.zeros((N, D), jnp.float32),
                                (wblocks, starts))
    dweight = dwbs.reshape(nb * bs, D)[:V].T.astype(weight.dtype)
    dtargets = np.zeros(targets.shape, jax.dtypes.float0)
    return dh.astype(hidden.dtype), dweight, dtargets


_blockwise_ce.defvjp(_ce_fwd, _ce_bwd)


def blockwise_cross_entropy(hidden, weight, targets, *,
                            block_size: int = 4096):
    """Per-token NLL of ``softmax(hidden @ weight)`` against ``targets``
    without materialising the logits.

    ``hidden``: ``[..., D]`` (bf16 or f32), ``weight``: ``[D, V]``,
    ``targets``: ``[...]`` int — returns f32 NLL of ``targets``' shape.
    Differentiable in ``hidden`` and ``weight``.

    Targets MUST be valid ids in ``[0, V)``: an out-of-range id (e.g. a
    -1 padding sentinel that was not masked out) gathers a zero logit
    from the padded block and returns a huge (~1e30-scale) NLL instead
    of raising — inside jit there is nothing to raise with.  Mask
    padding via the ``mask`` argument of ``lm_loss_fused``/your loss,
    never by feeding sentinel ids."""
    if not jnp.issubdtype(targets.dtype, jnp.integer):
        raise TypeError(f"targets must be integer ids, got {targets.dtype}")
    lead = targets.shape
    h2 = hidden.reshape(-1, hidden.shape[-1])
    t2 = targets.reshape(-1)
    if h2.shape[0] != t2.shape[0]:
        raise ValueError(f"hidden leading dims {hidden.shape[:-1]} != "
                         f"targets shape {lead}")
    nll = _blockwise_ce(h2, weight, t2, int(block_size))
    return nll.reshape(lead)


def _token_blocks(B: int, L: int, V: int) -> tuple[int, int]:
    """``(blocks, rows of the sequence a block)``: the fewest equal blocks
    of ``[B, l, V]`` float32 logits within ``SWEEP_LOGITS_BYTES``."""
    most = max(1, SWEEP_LOGITS_BYTES // (4 * V * B))
    nb = -(-L // most)
    return nb, -(-L // nb)


def _sweep(hidden, weight, targets, token_weight, *, grads: bool, over=()):
    """One device's rows: ``sum(token_weight * nll)`` and, with ``grads``,
    its gradients in ``token_weight`` (the ``nll``), ``hidden`` and
    ``weight``, all summed over the mesh axes ``over`` where the rows
    are a shard."""
    B, L, D = hidden.shape
    V = weight.shape[1]
    nb, lc = _token_blocks(B, L, V)
    if nb * lc > L:     # the last block's tail: zero rows of weight zero
        hidden, targets, token_weight = (
            jnp.pad(x, ((0, 0), (0, nb * lc - L)) + ((0, 0),) * (x.ndim - 2))
            for x in (hidden, targets, token_weight))
    put = jax.lax.dynamic_update_slice_in_dim

    def block(i, carry):
        h, t, w = (jax.lax.dynamic_slice_in_dim(x, i * lc, lc, axis=1)
                   for x in (hidden, targets, token_weight))
        logits = jnp.einsum("bld,dv->blv", h, weight,
                            preferred_element_type=jnp.float32)
        m = logits.max(axis=-1)
        lse = m + jnp.log(jnp.exp(logits - m[..., None]).sum(axis=-1))
        nll_c = lse - _target_logit(logits, t)
        loss = carry[0] + (w * nll_c).sum()
        if not grads:
            return (loss,)
        _, nll, dh, dw = carry
        # fenced: left to choose, XLA forms dlogits from the float32
        # logits inside EACH of the two matmuls below, an exp an element
        # for every tile of their outputs (the head alone on a v5e chip:
        # 93.2 ms a step against 85.9 with the bf16 block written once)
        dlogits = jax.lax.optimization_barrier(
            (_softmax_minus_onehot(logits, lse, t) * w[..., None]
             ).astype(hidden.dtype))
        dh_c = jnp.einsum("blv,dv->bld", dlogits, weight,
                          preferred_element_type=jnp.float32)
        dw = dw + jnp.einsum("bld,blv->dv", h, dlogits,
                             preferred_element_type=jnp.float32)
        return (loss, put(nll, nll_c, i * lc, axis=1),
                put(dh, dh_c.astype(dh.dtype), i * lc, axis=1), dw)

    init = (jnp.zeros((), jnp.float32),)
    if grads:
        init += (jnp.zeros(targets.shape, jnp.float32),
                 jnp.zeros_like(hidden), jnp.zeros((D, V), jnp.float32))
    with jax.named_scope("ce/sweep"):
        out = jax.lax.fori_loop(0, nb, block, init)
    loss = jax.lax.psum(out[0], over) if over else out[0]
    if not grads:
        return loss
    _, nll, dh, dw = out
    if over:
        dw = jax.lax.psum(dw, over)
    return loss, nll[:, :L], dh[:, :L], dw.astype(weight.dtype)


def _sweep_on(mesh, *, grads: bool):
    """``_sweep`` on ``mesh``: under ``shard_map`` over the axes that
    split the rows (``batch``, ``seq``), every device on its own."""
    rows = P() if mesh is None else logical_sharding(("batch", "seq"),
                                                     mesh).spec
    over = tuple(a for axis in rows if axis is not None
                 for a in (axis if isinstance(axis, tuple) else (axis,)))
    if not over:
        return functools.partial(_sweep, grads=grads)
    return jax.shard_map(
        functools.partial(_sweep, grads=grads, over=over), mesh=mesh,
        in_specs=(rows, P(), rows, rows),
        out_specs=(P(), rows, rows, P()) if grads else P(), check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _sweep_ce(hidden, weight, targets, token_weight, mesh):
    return _sweep_on(mesh, grads=False)(hidden, weight, targets, token_weight)


def _sweep_fwd(hidden, weight, targets, token_weight, mesh):
    loss, *grads = _sweep_on(mesh, grads=True)(hidden, weight, targets,
                                               token_weight)
    return loss, grads


def _sweep_bwd(mesh, grads, g):
    # the gradients stand since the forward; float32 for the product,
    # so that a cotangent that is no power of two is not rounded to bf16
    nll, dh, dw = ((g * x.astype(jnp.float32)).astype(x.dtype) for x in grads)
    return dh, dw, np.zeros(nll.shape, jax.dtypes.float0), nll


_sweep_ce.defvjp(_sweep_fwd, _sweep_bwd)


def sweep_cross_entropy(hidden, weight, targets, token_weight, *, mesh=None):
    """``sum(token_weight * nll)`` of ``softmax(hidden @ weight)`` against
    ``targets``, a float32 scalar, in one sweep over blocks of tokens
    against the WHOLE ``weight`` (the module docstring has what it costs).

    ``hidden``: ``[B, L, D]`` (bf16 or f32), ``weight``: ``[D, V]``, whole
    on every device of ``mesh``; ``targets``: ``[B, L]`` valid ids in
    ``[0, V)`` (as for :func:`blockwise_cross_entropy`: mask padding with
    a ``token_weight`` of zero, never with a sentinel id);
    ``token_weight``: ``[B, L]`` float32, e.g. ``mask / mask.sum()``.
    Differentiable in ``hidden``, ``weight`` and ``token_weight``; under
    differentiation the gradients are formed in the forward sweep.  On a
    ``mesh`` ``B`` and ``L`` must divide by the axes that shard
    ``("batch", "seq")``."""
    if not jnp.issubdtype(targets.dtype, jnp.integer):
        raise TypeError(f"targets must be integer ids, got {targets.dtype}")
    if hidden.ndim != 3 or not (hidden.shape[:2] == targets.shape
                                == token_weight.shape):
        raise ValueError(
            f"hidden {hidden.shape} must be [B, L, D] with targets "
            f"{targets.shape} and token_weight {token_weight.shape} [B, L]")
    return _sweep_ce(hidden, weight, targets,
                     token_weight.astype(jnp.float32), mesh)
