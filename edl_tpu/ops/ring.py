"""Ring attention: sequence-parallel exact attention over the ``sp``
mesh axis.

The long-context path the reference never had (SURVEY.md §5
"Long-context: absent").  Queries stay put; key/value blocks rotate
around the ring with ``ppermute`` while each shard folds every block
into a numerically-stable online softmax (the flash-attention
recurrence carried across devices).  Compute for block t overlaps the
transfer of block t+1 on ICI — the standard TPU ring schedule
(jax-ml.github.io/scaling-book; Liu et al., Ring Attention, 2023).

Exactness: identical result to full attention (tested against the
dense path), so it composes with causal masking by global positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30  # large-but-finite: avoids inf-inf=nan in the recurrence


def ring_attention(q, k, v, mesh: Mesh, *, causal: bool = False,
                   sm_scale: float | None = None, sp_axis: str = "sp",
                   batch_axes=("dp", "fsdp"), head_axis: str = "tp",
                   kv_chunk: int = 1024):
    """[B, L, H, D] global arrays, L sharded over ``sp_axis`` — exact
    attention without ever materialising a non-local [L, L] block pair.
    Call under jit; shard_map is applied internally.

    ``kv_chunk`` bounds the logits tile WITHIN each ring hop: the local
    k/v block is folded ceil(Lk / chunk) chunks at a time, the final
    chunk zero-padded and masked (never a degenerate divisor), so
    per-hop memory is O(Lq × chunk) instead of O(Lq × L/shards) — what
    keeps very long shards (few devices, long context) inside
    VMEM-friendly tiles.  0 disables."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    batch = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1) or None
    spec = P(batch, sp_axis, head_axis if mesh.shape.get(head_axis, 1) > 1 else None, None)

    local = functools.partial(_ring_local, axis=sp_axis,
                              n_shards=mesh.shape[sp_axis],
                              causal=causal, scale=scale,
                              kv_chunk=kv_chunk)
    f = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                  out_specs=spec, check_vma=False)
    return f(q, k, v)


def _ring_local(ql, kl, vl, *, axis: str, n_shards: int, causal: bool,
                scale: float, kv_chunk: int = 0):
    """Per-shard body: fold each rotating k/v block into the online
    softmax state (m: running max, l: running denominator, acc:
    unnormalised numerator), ``kv_chunk`` keys at a time.

    Chunking is ceil-division with a masked tail (never a degenerate
    divisor), and chunks are dynamic-sliced out of the block in place —
    no per-hop transposed copy of k/v."""
    B, Lq, H, D = ql.shape
    Lk = kl.shape[1]
    my = jax.lax.axis_index(axis)
    q_pos = my * Lq + jnp.arange(Lq)                     # global query rows
    chunk = Lk if kv_chunk <= 0 else min(kv_chunk, Lk)
    n_chunks = -(-Lk // chunk)
    pad = n_chunks * chunk - Lk

    # matmuls stay in the input dtype (bf16 on TPU -> full-rate MXU) with
    # f32 accumulation; only the softmax statistics are carried in f32
    m = jnp.full((B, H, Lq), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, Lq), jnp.float32)
    acc = jnp.zeros((B, Lq, H, D), jnp.float32)

    def fold(carry, kc, vc, mask):
        """mask [Lq, C] or None — rows the queries may attend to."""
        m, l, acc = carry
        logits = jnp.einsum("bqhd,bkhd->bhqk", ql, kc,
                            preferred_element_type=jnp.float32) * scale
        if mask is not None:
            logits = jnp.where(mask[None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(ql.dtype), vc,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    for step in range(n_shards):
        src = (my - step) % n_shards                     # owner of this block
        if n_chunks == 1:
            k_pos = src * Lk + jnp.arange(Lk)
            mask = (q_pos[:, None] >= k_pos[None, :]) if causal else None
            m, l, acc = fold((m, l, acc), kl, vl, mask)
        else:
            kp = jnp.pad(kl, ((0, 0), (0, pad), (0, 0), (0, 0))) \
                if pad else kl
            vp = jnp.pad(vl, ((0, 0), (0, pad), (0, 0), (0, 0))) \
                if pad else vl

            def chunk_fold(carry, i, kp=kp, vp=vp, src=src):
                kc = jax.lax.dynamic_slice_in_dim(kp, i * chunk, chunk, 1)
                vc = jax.lax.dynamic_slice_in_dim(vp, i * chunk, chunk, 1)
                local = i * chunk + jnp.arange(chunk)
                valid = local < Lk                       # tail padding
                mask = valid[None, :]
                if causal:
                    k_pos = src * Lk + local
                    mask = mask & (q_pos[:, None] >= k_pos[None, :])
                return fold(carry, kc, vc,
                            jnp.broadcast_to(mask, (Lq, chunk))), None

            (m, l, acc), _ = jax.lax.scan(chunk_fold, (m, l, acc),
                                          jnp.arange(n_chunks))
        if step + 1 < n_shards:                          # rotate k/v blocks
            kl = jax.lax.ppermute(kl, axis, perm)
            vl = jax.lax.ppermute(vl, axis, perm)

    denom = l.transpose(0, 2, 1)[..., None]              # [B, Lq, H, 1]
    out = acc / jnp.maximum(denom, 1e-30)
    return out.astype(ql.dtype)
