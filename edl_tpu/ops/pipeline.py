"""Pipeline parallelism over the ``pp`` mesh axis.

The reference had no pipelining at all (SURVEY.md §5: DP only); this
is part of the beyond-parity parallelism set (§7 step 7).  Design is
the TPU-native GPipe: stage parameters live on their pp shard (leading
``stage`` dim sharded over ``pp``), activations rotate between
neighbouring stages with ``lax.ppermute`` over ICI, and the schedule is
a statically-unrolled loop of ``M + S - 1`` ticks inside one
``shard_map`` — jax.grad differentiates straight through (ppermute's
transpose is the reverse rotation), so the backward schedule falls out
of AD instead of hand-written send/recv pairs.

Composability is the property beyond naive GPipe: the shard_map is
manual over ``pp`` ONLY — every other mesh axis (dp, fsdp, tp, ep)
stays in XLA's automatic (GSPMD) partitioning, so tensor-parallel
stage matmuls, fsdp parameter sharding and data-parallel batches
compose with the pipeline without hand-written collectives
(pp=2 × tp=2 × fsdp=2 is tested in tests/test_lm_example.py).

The bubble is the classic GPipe (S-1)/(M+S-1); raise
``n_microbatches`` to amortise.  Idle stages compute on garbage in
lockstep (see the in-body NOTE for why branching it away is unsound
with tp collectives inside the stage).

Why not 1F1B: measured (``examples/lm/pp_bench.py``) — with a
fixed global batch the AD-unrolled schedule's activation live-set is
FLAT-to-decreasing in M (per-tick stash shrinks as 1/M), so 1F1B's
memory cap buys under ~20% at sensible M while sharing GPipe's bubble;
raising M amortises the bubble for free precisely because memory does
not grow with it.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x, mesh: Mesh,
                   n_microbatches: int, axis: str = "pp",
                   batch_axes: tuple[str, ...] = ("dp", "fsdp")):
    """Run ``x`` through ``S`` pipelined stages.

    - ``stage_fn(params_s, h) -> h``: one stage's computation; must
      preserve the activation shape (classic equal-width pipeline).
    - ``stage_params``: pytree whose leaves have a leading ``S`` dim,
      sharded over ``axis`` (use logical axis "stage"); the remaining
      dims may carry tp/fsdp shardings — they stay under GSPMD.
    - ``x``: [B, ...] activations; the GLOBAL batch must divide by
      ``n_microbatches`` (and, as always, by the live batch axes).

    ``batch_axes`` is kept for call compatibility; batch partitioning
    now rides GSPMD (auto axes), not manual specs.

    Returns [B, ...] outputs, batch-sharded like ``x``.
    """
    del batch_axes
    S = mesh.shape[axis]
    M = n_microbatches
    if S == 1:  # no pipeline axis: just run the stages sequentially
        out, _ = jax.lax.scan(lambda h, p: (stage_fn(p, h), None),
                              x, stage_params)
        return out

    perm = [(i, (i + 1) % S) for i in range(S)]

    def per_device(params_local, x_mb):
        # params_local: this shard's stage slice — leading dim
        # n_layers/S; multiple layers per shard chain sequentially
        # (a "superstage"), so any layer count pipelines over any S
        n_local = len(jax.tree.leaves(params_local)[0])

        def superstage(h):
            for j in range(n_local):
                h = stage_fn(jax.tree.map(lambda a: a[j], params_local), h)
            return h

        stage_idx = jax.lax.axis_index(axis)
        carry = jnp.zeros_like(x_mb[0])     # activation arriving from prev
        outs = jnp.zeros_like(x_mb)         # filled on the LAST stage
        for t in range(M + S - 1):
            # NOTE: stages outside their active window compute on
            # garbage rather than branching it away — a lax.cond whose
            # predicate varies per pp shard deadlocks XLA's collective
            # rendezvous when the active branch contains tp collectives
            # (devices disagree about which channel comes next).  The
            # lockstep schedule's wall-clock is set by the active
            # stages either way; the garbage ticks cost only energy.
            # stage 0 injects microbatch t; later stages consume the wire
            inject = x_mb[min(t, M - 1)]
            h_in = jnp.where(stage_idx == 0, inject, carry)
            h_out = superstage(h_in)
            # last stage emits microbatch t-(S-1) at tick t
            m = t - (S - 1)
            if 0 <= m < M:
                is_last = stage_idx == S - 1
                outs = outs.at[m].set(jnp.where(is_last, h_out, outs[m]))
            carry = jax.lax.ppermute(h_out, axis, perm)
        # only the last stage holds real outputs; broadcast them to all
        # pp shards so the result is replicated over pp (psum of
        # one-hot-by-stage contributions)
        outs = jnp.where(jax.lax.axis_index(axis) == S - 1, outs,
                         jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    B = x.shape[0]
    assert B % M == 0, f"global batch {B} not divisible by {M} microbatches"
    x_mb = x.reshape((M, B // M) + x.shape[1:])

    # manual over pp only; every other axis stays automatic (GSPMD)
    out_mb = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
        axis_names=frozenset({axis}),
    )(stage_params, x_mb)
    return out_mb.reshape((B,) + x.shape[1:])
