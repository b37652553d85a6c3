"""What one layer keeps for a slot, and how the pool pages it: stated once.

A mixer kind (``TransformerConfig.attn_kind``) creates its cache
variables in ``models/transformer.py``; everything the serving path has
to know about them is one :class:`CacheClass` a layer, made here by
:func:`cache_classes` and read by the engine (refusals, snapshot policy,
the construction-time fit), the pool (``kv_cache.PagedKVCache``: its
buffers, the gather at a hit, the scatter at a commit, the wire format
of a migrated session) and the counters (``model_counters``).  Six
classes:

- :class:`HeadRows` - per-head key and value rows over the whole
  context (a "global" layer), paged by blocks;
- :class:`WindowRing` - the same rows in a ring of a window and a little
  more (``Block._ring_attention``).  A block deep in a chain would need
  keys the ring dropped long before the commit; what a prefix hit needs
  is the LAST WINDOW of the prefix, so it pages by **snapshots**:
  ``window`` positions ending at a chain node's end, read out of the
  slot's ring;
- :class:`FixedState` - a fixed-size recurrence (``Mamba2Mixer``:
  ``conv_state``, ``ssm_state``; ``KDAMixer``: ``conv_state``,
  ``kda_state``), paged by snapshots of the whole slot state.  A ring
  still holds a little history and can be snapshotted after the fact; a
  recurrence can be saved only at a position the program is AT, so its
  snapshot comes out of the prefill (``PagedKVCache.store_state``),
  never out of a slot (:class:`SsmState`, :class:`KdaState` and
  :class:`Mamba1State` (``Mamba1Mixer``: ``conv_state``, ``ssm_state``)
  differ in what their prefill's scan costs, nothing else);
- :class:`LatentRows` - one head-less row a token
  (``LatentAttention``: the normed latent and the key dims all heads
  share), paged by blocks as ONE buffer a layer: storing it as keys and
  as values would give back half of what the architecture saves.

- :class:`NoCache` - a layer that OWNS NOTHING (``GatedMemoryUnit``: it
  reads what a layer below computed for the same token): no leaf in a
  slot's cache, no buffer in the pool, nothing on the wire;
- :class:`BorrowedRows` - a layer that owns nothing and READS ANOTHER
  layer's rows (a "cross" layer over its ``lender``'s slab): the pool
  pages the lender's rows once, and a hit that gathered them has
  gathered what every borrower reads.

The table, a mixer kind a row:

==========  ====================  ==================  =================
kind        class                 a slot keeps        the pool pages by
==========  ====================  ==================  =================
global      :class:`HeadRows`     K / V rows          blocks
window      :class:`WindowRing`   a ring of K / V     ring snapshots
ssm         :class:`SsmState`     conv + state        state snapshots
kda         :class:`KdaState`     conv + state        state snapshots
latent      :class:`LatentRows`   one row a token     blocks
mamba1      :class:`Mamba1State`  conv + state        state snapshots
gmu         :class:`NoCache`      nothing             nothing
cross       :class:`BorrowedRows` nothing (lender's)  nothing
==========  ====================  ==================  =================

A new kind is one more class here (or one of these) and a row in
:func:`cache_classes`; the pool's methods and the engine name none.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from edl_tpu.ops import decode_attention, latent_attention


def _leaf_key(path) -> str:
    """A cache leaf's place under its layer, as the pool names it
    (``ssm/ssm_state``): the dictionary keys of its path."""
    return "/".join(k.key for k in path if hasattr(k, "key"))


def state_leaves(node) -> dict:
    """A layer's cache leaves but the index, by their place under the
    layer (``_leaf_key``)."""
    return {_leaf_key(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(node)[0]
            if not _leaf_key(path).endswith("cache_index")}


class CacheClass:
    """One layer's cache, as the serving path sees it.  ``node`` is the
    layer's subtree of a cache (arrays, or the ``eval_shape`` skeleton)
    and ``pool`` the layer's pool buffers.  A class states its buffers
    (:meth:`buffers`), how it pages (``snapshotted``, ``window``,
    ``from_slot``), what it says on the wire (``meta``, ``meta_key``),
    what it refuses (``no_rewind``, ``no_shard``) and what its
    multi-token call costs; ``pool_shapes``, ``load`` and ``store``
    follow from those for every class."""

    kind = "global"         # stats()' kv_slot_bytes_<kind>
    noun = ""               # "a <noun> configuration", in a refusal
    meta_key = None         # where export metadata lists the layers
    snapshotted = False     # pages by snapshots, not by blocks
    window = 0              # positions of the time axis a snapshot holds
    from_slot = True        # its pool entries are read out of a slot
    no_rewind = ""          # why a rejected draft cannot rewind it
    no_shard = ""           # why it does not shard over tp

    def buffers(self, node) -> dict:
        """``{pool buffer: (the leaf's place under the layer, its time
        axis in one lane)}``, refusing a layer it cannot page; no time
        axis (None): the whole leaf is one entry."""
        raise NotImplementedError

    def meta(self, node):
        """What export metadata says of its layout: the importer's must
        be equal."""
        raise NotImplementedError

    def sharded(self, node, tp: int) -> bool:
        """Whether its slabs and pool buffers split over ``tp`` (on axis
        1 of ``[lanes, heads, ...]``)."""
        return False

    def scan_bytes(self, width: int) -> int:
        """A lane's temporaries in a ``width``-token call that are not
        attention's (the construction-time fit adds them)."""
        return 0

    def scores_bytes(self, lanes: int, width: int, heads: int,
                     cache_len: int) -> int:
        """A lane's widest attention temporaries in a ``lanes x
        width`` call."""
        return 0

    def pool_shapes(self, node, block: int, n_blocks: int,
                    n_snaps: int) -> dict:
        """``{buffer: (shape, dtype)}`` of its pool: ``n_blocks``
        entries of ``block`` positions, or ``n_snaps`` of ``window``
        (of the whole leaf).  With ``n_blocks`` a chain's length and
        ``n_snaps`` 1: what that chain carries on the wire."""
        entries, span = ((n_snaps, self.window) if self.snapshotted
                         else (n_blocks, block))
        leaves, out = state_leaves(node), {}
        for buf, (key, t) in self.buffers(node).items():
            lane = list(leaves[key].shape[1:])
            if t is not None:
                if not 1 <= span <= lane[t]:
                    raise ValueError(
                        f"a {self.kind} layer's {key} holds {lane[t]} "
                        f"positions: fewer than the {span} of a pool entry")
                lane[t] = span
            out[buf] = ((entries, *lane), leaves[key].dtype)
        return out

    def _ring_slots(self, end, ring: int):
        """Slots of the ``window`` positions that end at ``end`` (traced)
        in a ring of ``ring``: position p lives at slot ``p % ring``.
        Positions before 0 fall on slots the ring's own position mask
        never reads."""
        return (end - self.window + jnp.arange(self.window)) % ring

    def load(self, node, pool, block_ids, n: int, block: int, prefix_len,
             snap_id):
        """``node`` (one lane) with a hit's prefix in it and its index
        at ``prefix_len`` (traced; under ``shard_map`` on a mesh the
        operands are the per-shard slices): ``n`` (padded) blocks at the
        front of each buffer's time axis, or snapshot ``snap_id``: the
        last window of the prefix back at its ring slots, a whole leaf
        as it stood after token ``prefix_len - 1``."""
        bufs = {key: (pool[buf], t)
                for buf, (key, t) in self.buffers(node).items()}

        def put(key, v):
            buf, t = bufs[key]
            if not self.snapshotted:
                rows = jnp.moveaxis(buf[block_ids], 0, t)   # [.., n, bs, ..]
                rows = rows.reshape(rows.shape[:t] + (n * block,)
                                    + rows.shape[t + 2:])
                at = (0,) + (slice(None),) * t + (slice(0, n * block),)
                return v.at[at].set(rows.astype(v.dtype))
            snap = buf[snap_id].astype(v.dtype)
            if t is None:
                return snap[None]
            at = ((slice(None),) * t + (self._ring_slots(
                prefix_len, v.shape[t + 1]),)
                  + (slice(None),) * (v.ndim - t - 2))
            return v[0].at[at].set(snap)[None]

        return jax.tree_util.tree_map_with_path(
            lambda path, v: (jnp.full_like(v, prefix_len)
                             if path[-1].key == "cache_index"
                             else put(_leaf_key(path), v)), node)

    def store(self, pool, node, slot, start, block_ids, n: int, block: int,
              snap_id, snap_end):
        """``pool`` with ``n`` blocks of slot ``slot`` from position
        ``start`` at ``block_ids``, or the window that ends at
        ``snap_end`` out of the slot's ring in entry ``snap_id`` (0:
        scratch).  Untouched where the entries do not come out of a
        slot (``from_slot``: the prefill saved them where it computed
        them, ``PagedKVCache.store_state``)."""
        if not (self.from_slot and (n or self.snapshotted)):
            return pool
        leaves, out = state_leaves(node), {}
        for buf, (key, t) in self.buffers(node).items():
            lane = jnp.take(leaves[key], slot, axis=0)
            if self.snapshotted:
                at = () if t is None else (
                    (slice(None),) * t + (self._ring_slots(
                        snap_end, lane.shape[t]),)
                    + (slice(None),) * (lane.ndim - t - 1))
                out[buf] = pool[buf].at[snap_id].set(lane[at])
                continue
            sizes = list(lane.shape)
            sizes[t] = n * block
            rows = jax.lax.dynamic_slice(
                lane, (0,) * t + (start,) + (0,) * (lane.ndim - t - 1), sizes)
            rows = rows.reshape(sizes[:t] + [n, block] + sizes[t + 1:])
            out[buf] = pool[buf].at[block_ids].set(jnp.moveaxis(rows, t, 0))
        return out


class HeadRows(CacheClass):
    """K entries keep the slab's ``[Hk, D, tokens]`` operand layout, V
    entries its ``[Hk, tokens, D]`` one."""

    def buffers(self, node):
        if set(node) != {"cached_key", "cached_value", "cache_index"}:
            raise ValueError(
                f"paged KV cache requires plain per-layer "
                f"cached_key/cached_value/cache_index state; a layer "
                f"carries {sorted(node)} and was named neither a state "
                f"layer nor a latent layer")
        return {"k": ("cached_key", 2), "v": ("cached_value", 1)}

    def meta(self, node):
        _, hk, d, _ = node["cached_key"].shape    # [slots, Hk, D, len]
        return [hk, d, str(np.dtype(node["cached_key"].dtype))]

    def sharded(self, node, tp):
        return tp > 1 and node["cached_key"].shape[1] % tp == 0

    def scores_bytes(self, lanes, width, heads, cache_len):
        # a multi-token call attends the whole slab under its mask: one
        # layer's [heads, width, cache_len] float32 scores; where those
        # could not exist (ops/decode_attention.prefix_tiled) a tile of
        # them at a time, with the probabilities and the carried
        # float32 output
        if decode_attention.prefix_tiled(width, heads, cache_len):
            tile = decode_attention.prefix_block(lanes, width, heads,
                                                 cache_len)
            return width * heads * (8 * tile + 8 * 128)
        return 4 * width * heads * cache_len


class WindowRing(HeadRows):
    kind, noun, meta_key = "window", "window", "ring_layers"
    snapshotted = True
    no_rewind = ("a rejected draft rewinds the cache index, and a window "
                 "layer's ring has already overwritten the positions the "
                 "rewound window needs")
    no_shard = "the window layers' snapshot pool has no sharded gather yet"

    def __init__(self, window: int):
        self.window = int(window)

    def sharded(self, node, tp):
        return False


class FixedState(CacheClass):
    kind, noun, meta_key = "state", "state-space", "state_layers"
    snapshotted, from_slot = True, False
    no_rewind = ("a rejected draft rewinds the cache index, and a "
                 "recurrent state that has taken the rejected tokens in "
                 "cannot be rewound")
    no_shard = ("the state layers' slot state and snapshot pool have no "
                "sharding yet")

    def __init__(self, cfg=None):
        self._cfg = cfg         # the decode configuration

    def buffers(self, node):
        return {key: (key, None) for key in sorted(state_leaves(node))}

    def meta(self, node):
        return {k: [list(v.shape[1:]), str(np.dtype(v.dtype))]
                for k, v in sorted(state_leaves(node).items())}


class SsmState(FixedState):
    def scan_bytes(self, width):
        # the projections' float32 copies of a lane's tokens and the
        # scan's [heads, chunk, chunk] blocks
        cfg, q = self._cfg, min(self._cfg.ssm_chunk, width)
        return 4 * (4 * width * (cfg.ssm_inner + cfg.ssm_conv_dim)
                    + 3 * cfg.ssm_heads * q * q)


class KdaState(FixedState):
    def scan_bytes(self, width):
        # the projections' float32 copies and the chunk's [heads, chunk,
        # chunk, key] decay differences
        cfg, q = self._cfg, min(self._cfg.kda_chunk, width)
        return 4 * (8 * width * 3 * cfg.kda_inner
                    + 3 * cfg.kda_inner * q * q)


class Mamba1State(FixedState):
    def scan_bytes(self, width):
        # the projections' and the scan's float32 rows of a lane's
        # tokens: x, z, dt, dt * x, y over the inner width
        return 4 * 6 * width * self._cfg.m1_inner


class NoCache(CacheClass):
    """A layer with no leaf in a slot's cache: nothing to page, to
    shard, to rewind or to export."""

    kind, noun = "empty", "stateless-mixer"

    def buffers(self, node):
        return {}

    def meta(self, node):
        return None


class BorrowedRows(NoCache):
    """A layer that reads ``lender``'s rows (a layer name) and keeps
    none: its one-token step is one more read of that slab."""

    kind, noun = "borrowed", "borrowed-rows"
    no_rewind = ("the verify step writes each slot's candidates at its "
                 "own index, and a borrowing layer's read follows one "
                 "position a lane")
    no_shard = "a borrowing layer's read of a sharded slab is not wired"

    def __init__(self, lender: str):
        self.lender = lender

    def scores_bytes(self, lanes, width, heads, cache_len):
        # it runs at a lane's last row alone (the last-position cut)
        return 4 * heads * cache_len


class LatentRows(CacheClass):
    kind, noun, meta_key = "latent", "latent attention", "latent_layers"
    no_rewind = ("the verify step writes each slot's candidates at its "
                 "own index, and the latent layers' multi-token path "
                 "writes at one")
    no_shard = "a latent row has no head axis to shard over tp"

    def __init__(self, cfg):
        self._cfg = cfg         # the decode configuration

    def buffers(self, node):
        (key, leaf), = state_leaves(node).items()   # [lanes, max_len, row]
        if leaf.ndim != 3:
            raise ValueError(f"a latent layer caches {leaf.shape}: not "
                             f"[slots, max_len, row]")
        return {"c": (key, 0)}

    def meta(self, node):
        (leaf,) = state_leaves(node).values()
        return [leaf.shape[-1], str(np.dtype(leaf.dtype))]

    def tiled(self, width: int) -> bool:
        """Whether a ``width``-token call's expanded path is the kernel
        ``latent_expand_tiled`` (the rule the model dispatches by)."""
        return self._cfg.mla_tiled(width)

    def tile(self, lanes: int, width: int, heads: int) -> int:
        """Rows a tile of the expanded path holds in a ``lanes x
        width`` call, on the path that call runs."""
        cfg = self._cfg
        return latent_attention.expand_block(
            lanes, width, heads, cfg.mla_nope_dim + cfg.mla_v_dim,
            cfg.max_len, cfg.dtype, self.tiled(width))

    def scores_bytes(self, lanes, width, heads, cache_len):
        # the expanded path attends a tile of rows at a time: its
        # float32 scores and probabilities and its expanded keys and
        # values, or, where the kernel runs it and keeps those on the
        # chip, the head-major queries, padded to a row's rest, and the
        # output
        cfg = self._cfg
        kv, item = cfg.mla_nope_dim + cfg.mla_v_dim, jnp.dtype(
            cfg.dtype).itemsize
        if self.tiled(width):
            return width * heads * (kv + cfg.mla_row - cfg.mla_rank) * item
        return self.tile(lanes, width, heads) * heads * (
            2 * 4 * width + kv * item)


ROWS = HeadRows()


_KINDS = {
    "global": lambda cfg: ROWS,
    "window": lambda cfg: WindowRing(cfg.attn_window),
    "ssm": SsmState,
    "kda": KdaState,
    "latent": LatentRows,
    "mamba1": Mamba1State,
    "gmu": lambda cfg: NoCache(),
    "cross": BorrowedRows,          # made of its lender's name
}


def cache_classes(cfg) -> dict:
    """``{layer name: CacheClass}`` of the decode configuration ``cfg``,
    in layer order; the layers of one kind share one object (a "cross"
    layer: those that borrow from one lender, the latest "global" layer
    below it)."""
    made, out, lender = {}, {}, None
    for i in range(cfg.num_layers):
        kind = key = cfg.attn_kind(i)
        if kind == "global":
            lender = f"layer_{i}"
        if kind == "cross":
            key = (kind, lender)
        if key not in made:
            made[key] = _KINDS[kind](lender if kind == "cross" else cfg)
        out[f"layer_{i}"] = made[key]
    return out


def cache_specs(classes: dict, shapes, tp: int) -> dict:
    """A cache's ``PartitionSpec`` tree (leaves are specs): a layer's
    slabs split over ``tp`` on axis 1 where its class shards
    (:meth:`CacheClass.sharded`), indices and the rest replicated."""
    return {name: jax.tree.map(
                lambda s, over=classes[name].sharded(node, tp):
                P(None, "tp") if over and s.ndim > 1 else P(), node)
            for name, node in shapes.items()}
