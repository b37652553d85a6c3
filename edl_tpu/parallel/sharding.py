"""Logical-axis sharding rules.

Models annotate arrays with *logical* axis names ("batch", "embed",
"heads", ...).  A ``ShardingRules`` table maps logical names to mesh
axes, so the same model code runs pure-DP, FSDP, TP, or any mix by
swapping the rules — the TPU-native analog of the reference switching
Fleet DistributedStrategy knobs (train_with_fleet.py:85-111) without
touching model code.

Weights carry ``embed -> fsdp``; activations never do.  A model names
its activations' axes too (``logical_constraint``; the transformer's
residual stream is ``("batch", "seq", None)``, its projections
``("batch", "seq", "heads" | "mlp")``), because for ``y[B/n, L, D] @
W[D/n, M]`` the partitioner is otherwise free to keep ``W``'s shard in
place, gather ``y`` to the whole batch and all-reduce the product:
tensor parallelism over the ``fsdp`` axis, a whole-batch all-reduce for
every matmul (a quarter of the four-chip step, PERF.md section 6,
PR 29).  With the activation fixed on the batch axes the only way left
is to gather ``W`` and reduce-scatter its gradient: ZeRO-3.  The same
names resolve to ``tp`` for Megatron's layout and to ``sp`` for
sequence shards; on one device every spec is empty and no constraint is
emitted.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Default logical→mesh table.  A logical name may map to a mesh axis, a
# tuple of mesh axes (sharded over both), or None (replicated).
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("dp", "fsdp"),   # global batch split over all data axes
    "seq": "sp",               # sequence/context parallelism
    "embed": "fsdp",           # zero-style param sharding
    "mlp": "tp",               # megatron column/row parallel
    "heads": "tp",
    "kv": None,
    "vocab": "tp",
    "expert": "ep",
    "expert_mlp": "tp",
    "layers": None,            # scanned-layer leading dim
    "stage": "pp",
    "conv_out": None,
    "table": "ep",             # CTR embedding tables (reference example/ctr)
    "norm": None,
}


@dataclass
class ShardingRules:
    """Logical axis name → mesh axis (or tuple / None)."""

    rules: dict[str, Any] = field(default_factory=lambda: dict(DEFAULT_RULES))

    def updated(self, **kw) -> "ShardingRules":
        r = dict(self.rules)
        r.update(kw)
        return ShardingRules(r)

    def spec(self, logical_axes: tuple[str | None, ...], mesh: Mesh) -> P:
        """Resolve logical axes to a PartitionSpec, dropping mesh axes of
        size 1 and axes that do not divide nothing (validation is left to
        jax)."""
        out = []
        used: set[str] = set()
        for name in logical_axes:
            axis = self.rules.get(name) if name else None
            if axis is None:
                out.append(None)
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            live = tuple(a for a in axes
                         if mesh.shape.get(a, 1) > 1 and a not in used)
            used.update(live)
            if not live:
                out.append(None)
            elif len(live) == 1:
                out.append(live[0])
            else:
                out.append(live)
        while out and out[-1] is None:
            out.pop()
        return P(*out)


def logical_sharding(logical_axes: tuple[str | None, ...], mesh: Mesh,
                     rules: ShardingRules | None = None) -> NamedSharding:
    rules = rules or ShardingRules()
    return NamedSharding(mesh, rules.spec(logical_axes, mesh))


def logical_constraint(x, logical_axes: tuple[str | None, ...],
                       mesh: Mesh | None,
                       rules: ShardingRules | None = None):
    """``with_sharding_constraint`` by logical names.  Without a mesh, or
    on a mesh of one device, nothing is emitted: ``x`` comes back as it
    is, so a model that names its activations' axes traces to the same
    program there as one that does not."""
    if mesh is None or mesh.size == 1:
        return x
    return jax.lax.with_sharding_constraint(
        x, logical_sharding(logical_axes, mesh, rules))


def tree_shardings(tree_logical, mesh: Mesh,
                   rules: ShardingRules | None = None):
    """Map a pytree of logical-axes tuples to NamedShardings."""
    rules = rules or ShardingRules()
    return jax.tree.map(
        lambda ax: logical_sharding(ax, mesh, rules),
        tree_logical,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )


def divisible_shardings(tree, shardings):
    """Replace any sharding whose spec does not divide its leaf's shape
    with a replicated one (serving-side guard: a config whose vocab or
    head count doesn't divide ``tp`` should serve correctly with that
    one tensor replicated, not crash — training's shard_init keeps
    strict validation so layout bugs surface loudly there)."""
    import math

    def fix(x, sh: NamedSharding):
        for dim, axes in enumerate(sh.spec):
            if axes is None:
                continue
            axes_t = axes if isinstance(axes, tuple) else (axes,)
            size = math.prod(sh.mesh.shape[a] for a in axes_t)
            if x.shape[dim] % size:
                return NamedSharding(sh.mesh, P())
        return sh

    return jax.tree.map(fix, tree, shardings)


def device_put_by_logical(tree, logical_rules, mesh: Mesh,
                          rules: ShardingRules | None = None):
    """Serving-side sharding recipe: map param paths to logical axes
    (``logical_rules`` — a model's LOGICAL_RULES list), resolve to mesh
    shardings, replicate anything that doesn't divide
    (:func:`divisible_shardings`), device_put.  The one place the
    lenient serve-time layout is defined — the engine and the teacher
    must never drift apart here."""
    from edl_tpu.models.logical import logical_axes_from_paths

    logical = logical_axes_from_paths(tree, logical_rules or [])
    shardings = tree_shardings(logical, mesh, rules or ShardingRules())
    return jax.device_put(tree, divisible_shardings(tree, shardings))


def shard_init(init_fn, tree_logical, mesh: Mesh,
               rules: ShardingRules | None = None):
    """Run ``init_fn`` under jit with output shardings so parameters are
    born sharded (never materialised replicated on one host)."""
    shardings = tree_shardings(tree_logical, mesh, rules)
    return jax.jit(init_fn, out_shardings=shardings)()


def allgather_flag(flag: int) -> np.ndarray:
    """One int32 per process, allgathered — the building block for
    per-step cross-host agreements (has-next in elastic_input, the eval
    loop's ragged-end handling)."""
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(
        np.asarray(flag, np.int32)))


def shard_host_batch(batch, mesh: Mesh, rules: ShardingRules | None = None):
    """Assemble per-host numpy batches into a global device array split
    on the batch axes.  This is the host→device hand-off the reference
    did via feed dicts (train_with_fleet.py:501-510); here each host
    contributes its shard and XLA sees one global array.
    """
    rules = rules or ShardingRules()

    def put(x):
        x = np.asarray(x)
        axes = ("batch",) + (None,) * (x.ndim - 1) if x.ndim else ()
        sharding = logical_sharding(axes, mesh, rules)
        if jax.process_count() == 1:
            return jax.device_put(x, sharding)
        return jax.make_array_from_process_local_data(sharding, x)

    return jax.tree.map(put, batch)
