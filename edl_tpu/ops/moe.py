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
  that order, and each projection is ONE grouped matmul over the sorted
  rows (``jax.lax.ragged_dot``, group sizes from a per-expert count:
  on TPU a Mosaic grouped-matmul kernel that visits only the tiles of
  experts that received rows, so a decode step reads the experts its
  batch touched and no others).  The rows are then unsorted, weighted
  by their gates and summed per token.  Nothing is ever dropped;
  ``token_mask``-ed (pad) positions route nowhere: zero weight, in no
  group, in no count.  No ``[B, S, E, C]`` tensor and no per-token
  weight gather exists on this path.  Serving an expert model through
  it (``serving/engine.py``) no longer drops: ``moe_prefill_drops``
  reads 0.
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
over the held experts) - the serving engine sums both into
``stats()``.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


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


def dropless_experts(x, gates, idx, w_gate, w_in, w_out, valid=None,
                     held_only: bool = False):
    """The dropless routed expert FFN over tokens ``x [T, M]`` with
    gates and expert indices ``[T, K]``: sort the ``T*K`` assignments
    by expert, one grouped matmul per projection over the sorted rows,
    unsort, weight and sum per token.  ``w_gate`` None = ungated.
    ``valid [T]`` bool marks real tokens; the others are sorted past
    every group (sentinel expert ``E``), so no expert computes them.
    So is a pair whose expert index is ``E`` or more: an expert this
    device does not hold (``MoEMLP.held``); its gate weighs nothing
    here.

    Returns ``(y [T, M] in x's dtype, sizes [E] int32)`` - ``sizes``
    the real assignments each expert received."""
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
    with jax.named_scope("moe/experts"):
        rows = x[order // K]                              # [T*K, M]
        h = _activate(
            jax.lax.ragged_dot(rows, w_in, sizes),
            None if w_gate is None
            else jax.lax.ragged_dot(rows, w_gate, sizes))
        out = jax.lax.ragged_dot(h, w_out, sizes)         # [T*K, M]
    with jax.named_scope("moe/combine"):
        out = jnp.where(in_group[:, None], out, 0)
        # unsort with the inverse permutation (a gather, not a scatter-
        # add) and sum each token's K rows in float32
        inverse = jnp.zeros_like(order).at[order].set(jnp.arange(T * K))
        out = out[inverse].reshape(T, K, -1).astype(jnp.float32)
        w = gates.astype(jnp.float32)
        if valid is not None:
            w = w * valid[:, None]
        if held_only:
            w = w * (idx < E)
        y = (out * w[..., None]).sum(axis=1)
    return y.astype(x.dtype), sizes


class MoEMLP(nn.Module):
    """Top-k routed expert FFN (drop-in for a transformer MLP block).

    Returns ``(y [B, S, M], aux_loss scalar)``.  Expert weights carry
    the ``expert`` leading logical axis; shard them over ``ep`` via the
    default rules (LOGICAL_RULES in models/transformer.py adds the
    matching param-path entries).

    ``capacity_factor <= 0`` selects the dropless path (module
    docstring) for every call: training, prefill, chunked prefill and
    decode run the same sort + grouped matmuls, nothing is dropped, and
    the layer's ``moe_stats`` are sown into ``intermediates``.

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
            y, sizes = dropless_experts(
                xt, gates.reshape(B * S, -1), idx.reshape(B * S, -1),
                None if w_gate is None else w_gate.astype(dtype),
                w_in.astype(dtype), w_out.astype(dtype), valid,
                held_only=bool(self.held))
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
