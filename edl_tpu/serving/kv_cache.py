"""Paged KV block pool with a prefix-reuse index (vLLM/SGLang, TPU-shaped).

The continuous-batching engine keeps one contiguous KV slab per decode
slot; every admission prefills the WHOLE prompt even when the fleet
serves a shared system prompt to every request and session affinity
routes a conversation's turns back to the replica that already computed
them.  This module is the missing half (ROADMAP item 2a): KV state,
chunked into fixed-size **blocks**, persists across requests in a
device-resident block pool and is found again through a token-exact
prefix index, so a new prompt's prefill starts from the longest cached
prefix instead of position 0.

Design (PagedAttention re-shaped for the engine's attention layout):

- **blocks, not pages-in-attention** — the decode attention kernel
  keeps reading one contiguous per-slot slab (``[Hk, D, max_len]``
  keys / ``[Hk, max_len, D]`` values: the two matmul operands,
  transformer.Block._decode_attention).  Paging happens at the
  *admission boundary*: a prefix hit gathers its block chain into the
  fresh slot slab in one fused jit (then prefills only the suffix), and
  a finished request's full blocks scatter back into the pool.  This
  trades one gather-copy per admission for leaving the bit-exact,
  profiled decode path untouched — on a TPU the copy is a contiguous
  HBM move that is orders of magnitude cheaper than the prefill it
  replaces;
- **hash-chain trie** — a block's identity is its token chunk *in its
  chain*: node = (parent, tuple(tokens[i*bs:(i+1)*bs])).  Two prompts
  sharing a prefix share nodes; token-exact matching keeps RoPE
  positions honest (a block is only reusable at the absolute position
  it was computed at, which the chain encodes by construction);
- **copy-on-write by immutability** — committed blocks are never
  written again; a reused chain is *copied* into the admitting slot's
  private slab, so a diverging continuation writes its own lanes and
  commits NEW blocks under new chain keys.  Sibling sessions can never
  observe each other's divergence (the smoke bit-compares outputs
  against fresh-cache runs);
- **refcount + LRU** — session pins refcount chain tails (the whole
  ancestor path is implicitly protected: a node with children is never
  evictable); allocation evicts the least-recently-used unpinned leaf
  when the free list runs dry, and an unallocatable commit is *skipped*
  (counted), never an error — the cache is an accelerator, not a
  correctness dependency;
- **migration-portable** — a pinned chain exports as (tokens, blob) and
  imports into another replica's pool, deduping against blocks the
  target already holds.  ``ReplicaServer.drain()`` uses this to hand
  live conversations to an adoptive replica instead of cold-starting
  them (doc/serving.md "Session KV migration");
- **mesh-native** (ISSUE 20) — on a tp mesh the pool buffers shard
  over the KV-head axis, exactly like the engine's slot slabs
  (``ContinuousBatcher._leaf_sharding``): every shard holds the SAME
  block ids for ITS heads, so the one host-side trie indexes all
  shards at once and block identity stays a host concept.  The
  gather/scatter/import jits lift through ``shard_map`` so every
  block move is shard-local by construction — no collective can
  appear in the pool path (doc/serving.md "Mesh-sharded paged KV").

- **two allocation classes** — a stack may mix global layers (whole
  context) with sliding-window layers, whose slot state is a ring of a
  window and a little more (``transformer.Block._ring_attention``).
  Global layers page by blocks as above.  A window layer keeps no
  blocks: a block deep in a chain would need keys the ring dropped
  long before the commit.  What a prefix hit needs of it is the LAST
  WINDOW of the prefix and nothing else, so window layers page by
  **snapshots**: ``window`` positions ending at a chain node's end,
  taken from the slot's ring when a commit ends there and when a
  prompt's prefill ends (at the deepest node the SAME prompt can match
  again), owned by that node, in a pool of their own (``n_snaps``
  entries, LRU among unpinned holders; entry 0 is scratch).  A chain is reusable as deep
  as its deepest snapshot-bearing node (``match`` truncates to it); the
  gather puts the snapshot back at ring slots ``position % ring`` and
  the global blocks at the front of the slabs, in the same fused jit.
  One trie, one LRU clock, one ``store_blocks`` dispatch for both.
- **layer state** - the snapshot class is not the window layers' alone.
  A state-space layer (``transformer.Mamba2Mixer``) keeps neither
  blocks nor a ring: a slot's state there is a fixed-size recurrence
  (``conv_state``, ``ssm_state``), and what a prefix hit needs of it is
  THE state after the prefix's last token.  A snapshot entry holds that
  for every state layer (and the last window for every window layer of
  the same stack), owned by the chain node it ends at, out of the same
  ``n_snaps`` entries under the same LRU.  A ring still holds a little
  history and can be snapshotted when a commit ends; a recurrence can
  be saved only at a position the program is AT, so a state layer's
  snapshot comes out of the prefill itself (``store_state``: the state
  the scan computed at the block edge), never out of a slot.  A
  delta-rule layer (``transformer.KDAMixer``: ``conv_state``,
  ``kda_state``) is a state layer like any other.
- **latent rows** - a latent attention layer
  (``transformer.LatentAttention``) caches ONE row a token, the normed
  key/value latent and the key dims all heads share: no head axis, keys
  and values the same bytes.  Its rows page by BLOCKS exactly as a
  global layer's keys and values do (same trie, same block ids, same
  LRU, same gather at a hit and scatter at a commit), but as one buffer
  a layer, ``[n_blocks, block, row]``: storing them as keys and as
  values would give back half of what the architecture saves.  A chain
  exports its latent blocks beside the other layers' (and, in a stack
  with state layers, the tail's snapshot).

Thread model: single-writer — every mutating call runs on the engine
thread (admission, finish-commit, import-task); ``export_chain`` runs
only after the engine thread has stopped.  Counters are plain ints read
racily by ``stats()`` (atomic loads; exactness there is not a contract).
"""

from __future__ import annotations

import heapq
from collections import OrderedDict

import numpy as np

from edl_tpu.utils.logger import get_logger

logger = get_logger(__name__)


def _pool_shapes(n_blocks: int, hk: int, d: int, block: int) -> dict:
    """One layer's pool buffers: K blocks keep the slab's [D, tokens]
    operand layout, V blocks its [tokens, D] one."""
    return {"k": (n_blocks, hk, d, block), "v": (n_blocks, hk, block, d)}


def _leaf_key(path) -> str:
    """A cache leaf's place under its layer, as the pool names it
    (``ssm/ssm_state``): the dictionary keys of its path."""
    return "/".join(k.key for k in path if hasattr(k, "key"))


def state_leaves(node) -> dict:
    """A state layer's cache leaves a snapshot holds (all but the
    index), by their place under the layer (``_leaf_key``)."""
    import jax
    return {_leaf_key(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(node)[0]
            if not _leaf_key(path).endswith("cache_index")}


def latent_leaf(node) -> tuple:
    """A latent layer's one cache buffer, ``(its place under the layer,
    the leaf [lanes, max_len, row])``."""
    (key, leaf), = state_leaves(node).items()
    return key, leaf


def pool_device_bytes(cache_shapes, block: int, n_blocks: int,
                      tp: int = 1, ring_layers=(), window: int = 0,
                      n_snaps: int = 0, state_layers=(),
                      latent_layers=()) -> int:
    """Per-device HBM bytes of the pool :class:`PagedKVCache` would
    allocate for this cache skeleton (KV heads split over ``tp`` where
    they divide, as the constructor shards them): ``n_blocks`` blocks
    of ``block`` tokens for every global layer, ``n_snaps`` snapshots
    of ``window`` tokens for every layer in ``ring_layers`` and of one
    lane's state for every layer in ``state_layers``; ``n_blocks``
    blocks of ``block`` rows, once, for every layer in
    ``latent_layers``.  Plain
    element counts: libtpu lays the 16-token minor dim of a K block out
    major-most rather than padding it to 128 lanes (measured on v5e:
    device bytes / nominal = 1.00 for both buffers, f32 and bf16)."""
    total = 0
    for name, node in cache_shapes.items():
        if name in state_layers:
            total += n_snaps * sum(
                int(np.prod(leaf.shape[1:], dtype=np.int64))
                * np.dtype(leaf.dtype).itemsize
                for leaf in state_leaves(node).values())
            continue
        if name in latent_layers:
            _, leaf = latent_leaf(node)
            total += (n_blocks * block * leaf.shape[-1]
                      * np.dtype(leaf.dtype).itemsize)
            continue
        _, hk, d, _ = node["cached_key"].shape
        hk = hk // tp if tp > 1 and hk % tp == 0 else hk
        item = np.dtype(node["cached_key"].dtype).itemsize
        shapes = (_pool_shapes(n_snaps, hk, d, window)
                  if name in ring_layers
                  else _pool_shapes(n_blocks, hk, d, block))
        total += sum(int(np.prod(shape, dtype=np.int64)) * item
                     for shape in shapes.values())
    return total


class _Node:
    """One committed block in the prefix trie."""

    __slots__ = ("chunk", "block_id", "parent", "children", "pins",
                 "last_use", "snap")

    def __init__(self, chunk: tuple, block_id: int, parent: "_Node | None"):
        self.chunk = chunk
        self.block_id = block_id
        self.parent = parent
        self.children: dict[tuple, _Node] = {}
        self.pins = 0
        self.last_use = 0
        self.snap = 0       # layer-state snapshot ending here (0: none)


class PagedKVCache:
    """Device block pools (one k + one v buffer per global layer, one
    buffer of rows per latent layer, snapshot entries for window and
    state layers) plus the host-side prefix trie, free list, session
    pins and eviction policy.

    ``cache_shapes`` is the engine's per-slot cache skeleton
    (``{layer: {cached_key, cached_value, cache_index}}`` eval_shape
    tree; a state or latent layer's node is what its mixer keeps) —
    pool layouts are derived from it so the gather/scatter jits line up
    with the slot slabs by construction.

    ``mesh`` (optional) shards the pool buffers over the mesh's ``tp``
    axis on the KV-head dim, mirroring the engine's slot-slab sharding
    predicate per layer — every shard keeps the same block indices, so
    the host trie / free list / pins need no changes at all.
    """

    def __init__(self, cache_shapes, block: int, n_blocks: int,
                 max_sessions: int, mesh=None, ring_layers=(),
                 window: int = 0, n_snaps: int = 0, state_layers=(),
                 latent_layers=()):
        import jax
        import jax.numpy as jnp

        if block < 1:
            raise ValueError(f"kv block size must be >= 1, got {block}")
        if n_blocks < 1:
            raise ValueError(f"kv pool needs >= 1 block, got {n_blocks}")
        self.block = int(block)
        self.n_blocks = int(n_blocks)
        self._layers: list[str] = sorted(cache_shapes)
        # the window class (module docstring): layers whose slot state
        # is a ring, the window a snapshot holds, and how many there are
        self._ring = frozenset(ring_layers)
        # the state class: layers whose slot state is a recurrence
        self._state = frozenset(state_layers)
        # the latent class: layers that cache one head-less row a token
        self._latent = frozenset(latent_layers)
        self._snapped = bool(self._ring or self._state)
        self.window = int(window) if self._ring else 0
        self.n_snaps = int(n_snaps) if self._snapped else 0
        if self._snapped and ((self._ring and self.window < 1)
                              or self.n_snaps < 2):
            raise ValueError(
                f"window layers {sorted(self._ring)} and state layers "
                f"{sorted(self._state)} need a window and at least 2 "
                f"snapshots, got {window} and {n_snaps}")
        if self._snapped and mesh is not None:
            raise ValueError(
                "a paged KV cache with window or state layers is not "
                "sharded over a mesh: the snapshot pool has no sharded "
                "gather yet")
        if self._latent and mesh is not None:
            raise ValueError(
                "a paged KV cache with latent layers is not sharded over a "
                "mesh: a latent row has no head axis to shard over tp")
        self._layout: dict[str, tuple] = {}
        # a latent layer's buffer: {layer: (leaf's place, row, dtype)}
        self._latent_layout: dict[str, tuple] = {}
        # a state layer's snapshot leaves: {leaf: (shape of a lane, dtype)}
        self._state_layout: dict[str, dict] = {}
        for name in self._layers:
            node = cache_shapes[name]
            if name in self._state:
                self._state_layout[name] = {
                    k: (tuple(v.shape[1:]), v.dtype)
                    for k, v in sorted(state_leaves(node).items())}
                continue
            if name in self._latent:
                key, leaf = latent_leaf(node)   # [slots, max_len, row]
                if leaf.ndim != 3 or block > leaf.shape[1]:
                    raise ValueError(
                        f"latent layer {name} caches {leaf.shape}: not "
                        f"[slots, max_len >= {block}, row]")
                self._latent_layout[name] = (key, leaf.shape[-1], leaf.dtype)
                continue
            if set(node) != {"cached_key", "cached_value", "cache_index"}:
                raise ValueError(
                    f"paged KV cache requires plain per-layer "
                    f"cached_key/cached_value/cache_index state; layer "
                    f"{name} carries {sorted(node)} and was named neither "
                    f"a state layer nor a latent layer")
            k = node["cached_key"]          # [slots, Hk, D, max_len]
            _, hk, d, length = k.shape
            if name in self._ring:
                if length < self.window:
                    raise ValueError(
                        f"layer {name}'s ring holds {length} positions, "
                        f"fewer than the window {self.window}")
            elif block > length:
                raise ValueError(
                    f"kv block {block} exceeds cache length {length}")
            self._layout[name] = (hk, d, k.dtype)
        self._mesh = mesh
        self._tp = dict(mesh.shape).get("tp", 1) if mesh is not None else 1
        # per-layer: shard the pool over ``tp`` on the KV-head axis
        # exactly when the engine shards that layer's slot slabs
        # (ContinuousBatcher._leaf_sharding: axis-1 divisible by tp) —
        # per-shard pools with IDENTICAL block ids, so a block move
        # never crosses shards and one host trie covers every shard
        self._layer_sharded = {
            name: self._tp > 1 and hk % self._tp == 0
            for name, (hk, d, _) in self._layout.items()}
        self._layer_sharded.update(
            dict.fromkeys(self._state | self._latent, False))
        # block 0 is a reserved scratch block (never allocated) so a
        # zero-filled block-id vector can never alias live state
        self.pool = {
            name: {ax: jnp.zeros(shape, dtype) for ax, shape in (
                _pool_shapes(self.n_snaps, hk, d, self.window)
                if name in self._ring
                else _pool_shapes(n_blocks, hk, d, block)).items()}
            for name, (hk, d, dtype) in self._layout.items()
        }
        for name, leaves in self._state_layout.items():
            self.pool[name] = {
                k: jnp.zeros((self.n_snaps,) + shape, dtype)
                for k, (shape, dtype) in leaves.items()}
        for name, (_, row, dtype) in self._latent_layout.items():
            self.pool[name] = {"c": jnp.zeros((n_blocks, block, row), dtype)}
        if mesh is not None:
            from jax.sharding import NamedSharding

            self.pool = jax.device_put(self.pool, {
                name: {ax: NamedSharding(mesh, spec)
                       for ax, spec in node.items()}
                for name, node in self._pool_specs().items()})
        self._free: list[int] = list(range(n_blocks - 1, 0, -1))
        # snapshot 0 is scratch, like block 0; holders in LRU order
        self._snap_free: list[int] = list(range(self.n_snaps - 1, 0, -1))
        self._snap_lru: "OrderedDict[_Node, None]" = OrderedDict()
        self.snap_skips = 0
        self.committed: list[_Node] = []    # the last commit's chain
        self._root = _Node((), 0, None)
        self._nodes: set[_Node] = set()         # every live non-root node
        # lazy min-heap of eviction candidates (last_use, seq, node):
        # pushed on every candidate transition (created childless,
        # unpinned, child evicted), validated on pop — a full pool's
        # steady-state commit must not rescan every node per block
        self.last_cut = 0       # tokens the last match() gave up (above)
        self._evict_heap: list[tuple[int, int, _Node]] = []
        self._heap_seq = 0
        self._sessions: "OrderedDict[str, _Node]" = OrderedDict()
        self._session_snaps: dict[str, _Node] = {}
        self._max_sessions = max(1, int(max_sessions))
        self._clock = 0
        self._jit_cache: dict[tuple, object] = {}
        self._zero = jnp.zeros((), jnp.int32)
        self._jax = jax
        self._jnp = jnp
        # -- counters (engine stats mirror these) --
        self.evictions = 0
        self.commit_skips = 0

    # -- host index ----------------------------------------------------------
    def _chunks(self, tokens, n: int):
        """The first ``n`` blocks of ``tokens`` as trie keys, one at a
        time: a walk that stops at its first miss has paid for one key,
        not for the whole prompt (2 ms of host Python for 6k tokens, on
        every tick a cold document waits at the queue's front)."""
        bs = self.block
        return (tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
                for i in range(n))

    def match(self, tokens) -> list[_Node]:
        """Longest committed chain covering full-block prefixes of
        ``tokens``, capped so at least ONE prompt token is always left
        to prefill (the admission needs its logits to sample from)."""
        max_blocks = (len(tokens) - 1) // self.block
        node = self._root
        chain: list[_Node] = []
        for chunk in self._chunks(tokens, max_blocks):
            child = node.children.get(chunk)
            if child is None:
                break
            chain.append(child)
            node = child
        # what the layer state cut off a hit the blocks alone would give
        self.last_cut = len(chain) * self.block
        chain = self.reusable(chain)
        self.last_cut -= len(chain) * self.block
        self._clock += 1
        for nd in chain:
            nd.last_use = self._clock
        if chain and self._snapped:
            self._snap_lru.move_to_end(chain[-1])
        return chain

    def reusable(self, chain: list[_Node]) -> list[_Node]:
        """``chain`` as deep as a prefix hit, here or on the replica a
        session migrates to, can start from: with window or state
        layers, down to its deepest node that owns a snapshot (the
        window layers' last window ends there, and the state layers'
        state is the one after its last token)."""
        if self._snapped:
            while chain and not chain[-1].snap:
                chain = chain[:-1]
        return chain

    def shorter(self, chain: list[_Node]) -> list[_Node]:
        """The next shorter reusable chain (the engine shortens a hit
        until prefix + suffix bucket fits the cache)."""
        return self.reusable(chain[:-1])

    # -- window snapshots ----------------------------------------------------
    def snap_alloc(self) -> int:
        """A free snapshot entry for the caller to write
        (``store_blocks``) and then ``snap_attach`` or ``snap_release``:
        0 when no layer keeps snapshots or every entry belongs to a
        pinned chain (counted)."""
        if not self._snapped:
            return 0
        if self._snap_free:
            return self._snap_free.pop()
        victim = next((nd for nd in self._snap_lru if not nd.pins), None)
        if victim is None:
            self.snap_skips += 1
            return 0
        return self._drop_snap(victim)

    def snap_attach(self, node: "_Node | None", sid: int) -> None:
        """``node`` owns snapshot ``sid`` from now on; an entry that
        finds no node, or a node that has one, goes back."""
        if not sid:
            return
        if node is None or node is self._root or node.snap:
            self._snap_free.append(sid)
            return
        node.snap = sid
        self._snap_lru[node] = None

    def snap_for(self, node: "_Node | None") -> int:
        """``snap_alloc`` + ``snap_attach`` for a node that has no
        snapshot yet; 0 when it has one."""
        if node is None or node.snap:
            return 0
        sid = self.snap_alloc()
        self.snap_attach(node, sid)
        return sid

    def _drop_snap(self, nd: _Node) -> int:
        sid, nd.snap = nd.snap, 0
        self._snap_lru.pop(nd, None)
        return sid

    def snaps_used(self) -> int:
        return len(self._snap_lru)

    def commit(self, tokens) -> tuple[int, list[int], "_Node | None"]:
        """Extend the trie with every full block of ``tokens`` that is
        not already committed.  Returns ``(first_new_block_index,
        new_block_ids, tail_node)`` — the caller owns writing the new
        blocks' KV into the pool (``scatter_fn``).  A dry pool truncates
        the commit (counted in ``commit_skips``) rather than failing."""
        n_full = len(tokens) // self.block
        node = self._root
        chunks = list(self._chunks(tokens, n_full))
        # the nodes of this commit's chain, root-first: ``committed[d -
        # 1]`` ends d blocks down (the engine hangs a prompt's window
        # snapshot on one of them)
        self.committed = []
        i = 0
        while i < n_full:
            child = node.children.get(chunks[i])
            if child is None:
                break
            node = child
            self.committed.append(node)
            i += 1
        start = i
        new_ids: list[int] = []
        for chunk in chunks[start:]:
            child = self._extend(node, chunk)
            if child is None:
                break
            node = child
            self.committed.append(node)
            new_ids.append(child.block_id)
        tail = node if node is not self._root else None
        return start, new_ids, tail

    def _extend(self, node: _Node, chunk: tuple) -> "_Node | None":
        """Attach ONE new child block under ``node`` — the single place
        the trie grows (commit + import share it so the eviction-guard
        invariants can't drift).  The walk tail is childless until the
        new child attaches, so it is pinned across the allocation to
        keep eviction from taking it.  Returns None on a dry pool — the
        caller truncates (counted), never fails."""
        node.pins += 1
        bid = self._alloc()
        self._unpin(node)
        if bid is None:
            self.commit_skips += 1
            return None
        child = _Node(chunk, bid, node)
        node.children[chunk] = child
        self._nodes.add(child)
        self._clock += 1
        child.last_use = self._clock
        self._heap_push(child)
        return child

    def _heap_push(self, nd: _Node) -> None:
        """Enter ``nd`` as an eviction candidate if it is one right now
        (childless, unpinned, non-root).  Entries go stale when the node
        is touched, gains a child or pins, or is evicted — ``_alloc``
        revalidates on pop, so pushing eagerly is always safe."""
        if nd is self._root or nd.children or nd.pins:
            return
        self._heap_seq += 1
        heapq.heappush(self._evict_heap, (nd.last_use, self._heap_seq, nd))

    def _unpin(self, nd: _Node) -> None:
        """Drop one pin; a node whose last pin leaves while it is a
        leaf becomes evictable and must re-enter the heap (its pinned
        pops were dropped without re-push)."""
        nd.pins -= 1
        self._heap_push(nd)

    def _alloc(self) -> int | None:
        if self._free:
            return self._free.pop()
        heap = self._evict_heap
        while heap:
            last_use, _, nd = heapq.heappop(heap)
            parent = nd.parent
            if parent is None or parent.children.get(nd.chunk) is not nd:
                continue                      # already evicted
            if nd.children or nd.pins:
                continue  # not a leaf / pinned; transitions re-push it
            if nd.last_use != last_use:
                self._heap_push(nd)           # touched since push: re-rank
                continue
            del parent.children[nd.chunk]
            self._nodes.discard(nd)
            if nd.snap:
                self._snap_free.append(self._drop_snap(nd))
            if parent is not self._root and not parent.children:
                self._heap_push(parent)       # newly a leaf
            self.evictions += 1
            return nd.block_id
        return None

    # -- session pins --------------------------------------------------------
    def pin_session(self, session: str, node: _Node) -> None:
        self.unpin_session(session)
        node.pins += 1
        self._sessions[session] = node
        # with state layers the chain's snapshot sits where its last
        # PROMPT ended, above the tail: the session holds that too
        holder = node
        while self._state and holder is not None and not holder.snap:
            holder = holder.parent
        if holder is not None and holder is not node and holder.snap:
            holder.pins += 1
            self._session_snaps[session] = holder
        while len(self._sessions) > self._max_sessions:
            self.unpin_session(next(iter(self._sessions)))

    def unpin_session(self, session: str) -> None:
        for held in (self._sessions, self._session_snaps):
            node = held.pop(session, None)
            if node is not None:
                self._unpin(node)

    def sessions(self) -> list[str]:
        """Pinned session ids — engine-thread / post-stop callers only
        (iterating the OrderedDict races live pinning; cross-thread
        pollers go through ``ContinuousBatcher.kv_pinned_sessions``,
        which treats the resulting RuntimeError as "retry later")."""
        return list(self._sessions)

    def session_count(self) -> int:
        """Racy-read-safe session count (``len`` is atomic under the
        GIL, unlike iteration) — what ``stats()`` mirrors from other
        threads."""
        return len(self._sessions)

    def chain_of(self, session: str) -> list[_Node]:
        node = self._sessions.get(session)
        chain: list[_Node] = []
        while node is not None and node is not self._root:
            chain.append(node)
            node = node.parent
        chain.reverse()
        return chain

    @staticmethod
    def chain_tokens(chain: list[_Node]) -> list[int]:
        return [t for nd in chain for t in nd.chunk]

    # -- stats ---------------------------------------------------------------
    def blocks_used(self) -> int:
        return self.n_blocks - 1 - len(self._free)

    def blocks_free(self) -> int:
        return len(self._free)

    # -- mesh sharding -------------------------------------------------------
    def _pool_specs(self):
        """Per-layer PartitionSpec tree for the pool's k/v buffers —
        the shard_map in/out specs and the constructor's device_put.
        Blocks stay whole on every shard (axis 0 unsharded); only the
        KV-head axis splits, and only for layers the engine shards."""
        from jax.sharding import PartitionSpec as P

        return {name: {"k": P(None, "tp") if self._layer_sharded[name]
                       else P(),
                       "v": P(None, "tp") if self._layer_sharded[name]
                       else P()}
                for name in self._layers}

    def _cache_specs(self):
        """PartitionSpec tree for a full engine cache passed into the
        scatter jit (slot slabs shard like the pool; indices are
        replicated)."""
        from jax.sharding import PartitionSpec as P

        out = {}
        for name in self._layers:
            kv = P(None, "tp") if self._layer_sharded[name] else P()
            out[name] = {"cached_key": kv, "cached_value": kv,
                         "cache_index": P()}
        return out

    def _pool_jit(self, fn, in_specs, donate=()):
        """jit ``fn`` over pool-shaped operands; on a mesh, lift it
        through ``shard_map`` first so every block move is shard-local
        by construction (per-shard pools, identical indices — the body
        can never emit a collective).  ``check_vma=False``: the bodies
        are all gathers/scatters by replicated indices, which the
        replication checker cannot prove through."""
        if self._mesh is None:
            return self._jax.jit(fn, donate_argnums=donate)
        wrapped = self._jax.shard_map(
            fn, mesh=self._mesh, in_specs=in_specs,
            out_specs=self._pool_specs(), check_vma=False)
        return self._jax.jit(wrapped, donate_argnums=donate)

    # -- jitted device ops ---------------------------------------------------
    def _ring_slots(self, end, ring: int):
        """Ring slots of the ``window`` positions that end at ``end``
        (traced): position p lives at slot ``p % ring``.  Positions
        before 0 fall on slots the ring's own position mask never
        reads."""
        return (end - self.window + self._jnp.arange(self.window)) % ring

    def load_prefix_into(self, cache, pool, block_ids, n: int, prefix_len,
                         snap_id=0):
        """Pure helper traced INSIDE the engine's reuse-prefill jit
        (``pool`` is the traced argument — never read device state off
        ``self`` under a trace): gather ``n`` (padded) blocks into the
        front of a fresh one-lane cache and set its index to the traced
        ``prefix_len`` (<= ``n * block``; the scratch-padded tail lands
        beyond it and is overwritten or masked before any query can
        attend it).  Window layers take snapshot ``snap_id`` (the
        chain tail's) into the ring slots of the ``window`` positions
        before ``prefix_len``: exactly the last window of the prefix.
        State layers take the same entry whole: the recurrence as it
        stood after token ``prefix_len - 1``.  Latent layers gather
        their blocks of rows as global layers gather keys and values."""
        jnp = self._jnp
        bs = self.block
        out = {}
        for name in self._layers:
            node = cache[name]
            if name in self._state:
                out[name] = self._jax.tree_util.tree_map_with_path(
                    lambda path, v, name=name: (
                        jnp.full_like(v, prefix_len)
                        if path[-1].key == "cache_index" else
                        pool[name][_leaf_key(path)][snap_id][None].astype(
                            v.dtype)), node)
                continue
            if name in self._latent:
                rows = pool[name]["c"][block_ids]         # [n, bs, row]
                rows = rows.reshape(n * bs, rows.shape[-1])
                out[name] = self._jax.tree_util.tree_map_with_path(
                    lambda path, v, rows=rows: (
                        jnp.full_like(v, prefix_len)
                        if path[-1].key == "cache_index" else
                        v.at[0, :n * bs].set(rows.astype(v.dtype))), node)
                continue
            if name in self._ring:
                ring = node["cached_key"].shape[-1]
                slots = self._ring_slots(prefix_len, ring)
                k = pool[name]["k"][snap_id]          # [Hk, D, window]
                v = pool[name]["v"][snap_id]          # [Hk, window, D]
                out[name] = {
                    "cached_key": node["cached_key"][0].at[:, :, slots].set(
                        k.astype(node["cached_key"].dtype))[None],
                    "cached_value": node["cached_value"][0].at[
                        :, slots, :].set(
                        v.astype(node["cached_value"].dtype))[None],
                    "cache_index": jnp.full_like(node["cache_index"],
                                                 prefix_len),
                }
                continue
            k = pool[name]["k"][block_ids]            # [n, Hk, D, bs]
            k = jnp.moveaxis(k, 0, 2).reshape(
                k.shape[1], k.shape[2], n * bs)
            v = pool[name]["v"][block_ids]            # [n, Hk, bs, D]
            v = jnp.moveaxis(v, 0, 1).reshape(
                v.shape[1], n * bs, v.shape[3])
            out[name] = {
                "cached_key": node["cached_key"].at[0, :, :, :n * bs].set(
                    k.astype(node["cached_key"].dtype)),
                "cached_value": node["cached_value"].at[0, :, :n * bs, :].set(
                    v.astype(node["cached_value"].dtype)),
                "cache_index": jnp.full_like(node["cache_index"],
                                             prefix_len),
            }
        return out

    def _scatter_fn(self, n: int):
        """jit per new-block count: copy ``n`` contiguous blocks of one
        slot's slab (starting at traced byte position ``start``) into
        the pool at ``block_ids``.  The pool is donated — committing
        never copies it."""
        key = ("scatter", n)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn
        jax, jnp = self._jax, self._jnp
        bs = self.block
        layers = self._layers

        ring_layers = self._ring

        def scatter(pool, cache, slot, start, block_ids, snap_id, snap_end):
            out = {}
            for name in layers:
                if name in self._state:
                    # a recurrence is saved where the prefill computed
                    # it (store_state), never out of a slot
                    out[name] = pool[name]
                    continue
                if name in ring_layers:
                    # the window that ends at snap_end, out of the
                    # slot's ring, into snapshot snap_id (0: scratch)
                    k_lane = jnp.take(cache[name]["cached_key"], slot, axis=0)
                    v_lane = jnp.take(cache[name]["cached_value"], slot,
                                      axis=0)
                    slots = self._ring_slots(snap_end, k_lane.shape[-1])
                    out[name] = {
                        "k": pool[name]["k"].at[snap_id].set(
                            k_lane[:, :, slots]),
                        "v": pool[name]["v"].at[snap_id].set(
                            v_lane[:, slots, :]),
                    }
                    continue
                if not n:
                    out[name] = pool[name]
                    continue
                if name in self._latent:
                    key, row, _ = self._latent_layout[name]
                    lane = jnp.take(state_leaves(cache[name])[key], slot,
                                    axis=0)               # [max_len, row]
                    rows = jax.lax.dynamic_slice(lane, (start, 0),
                                                 (n * bs, row))
                    out[name] = {"c": pool[name]["c"].at[block_ids].set(
                        rows.reshape(n, bs, row))}
                    continue
                # head/feature extents come from the OPERANDS, not the
                # global layout: under shard_map this body sees the
                # per-shard slice (hk/tp heads), and the slab/pool pair
                # agree per shard by construction
                k_lane = jnp.take(cache[name]["cached_key"], slot, axis=0)
                hk, d = k_lane.shape[0], k_lane.shape[1]
                k_sl = jax.lax.dynamic_slice(k_lane, (0, 0, start),
                                             (hk, d, n * bs))
                k_blocks = jnp.moveaxis(k_sl.reshape(hk, d, n, bs), 2, 0)
                v_lane = jnp.take(cache[name]["cached_value"], slot, axis=0)
                v_sl = jax.lax.dynamic_slice(v_lane, (0, start, 0),
                                             (hk, n * bs, d))
                v_blocks = jnp.moveaxis(v_sl.reshape(hk, n, bs, d), 1, 0)
                out[name] = {
                    "k": pool[name]["k"].at[block_ids].set(k_blocks),
                    "v": pool[name]["v"].at[block_ids].set(v_blocks),
                }
            return out

        from jax.sharding import PartitionSpec as P

        fn = self._pool_jit(
            scatter, (self._pool_specs(), self._cache_specs(),
                      P(), P(), P(), P(), P()), donate=(0,))
        self._jit_cache[key] = fn
        return fn

    def store_blocks(self, cache, slot: int, start_block: int,
                     block_ids: list[int], snap: tuple[int, int] = (0, 0),
                     warm: bool = False) -> None:
        """Write blocks ``[start_block, start_block + len(ids))`` of the
        slot's slab into the pool and, for window layers, snapshot
        ``snap = (id, end position)`` out of the slot's rings (one
        dispatch for both; id 0 writes the scratch entry).  ``warm``
        runs the program though there is nothing to write (into the
        scratch entries: ``ContinuousBatcher.warm``)."""
        if not block_ids and not snap[0] and not warm:
            return
        jnp = self._jnp
        self.pool = self._scatter_fn(len(block_ids))(
            self.pool, cache, jnp.asarray(slot, jnp.int32),
            jnp.asarray(start_block * self.block, jnp.int32),
            jnp.asarray(block_ids, jnp.int32),
            self.snap_arg(snap[0]), self.snap_arg(snap[1]))

    def store_state(self, snap, lane: int, sid: int) -> None:
        """The state layers' part of snapshot ``sid``: lane ``lane`` of
        ``snap`` ``{layer: {leaf: [K, ...]}}`` (leaves named as the pool
        names them, ``state_leaves``), the states a prefill
        program computed at its lanes' block edges
        (``transformer.Mamba2Mixer``'s ``snap`` collection).  One
        dispatch; the pool is donated."""
        fn = self._jit_cache.get("store_state")
        if fn is None:
            def put(pool, snap, lane, sid):
                out = dict(pool)
                for name in self._state:
                    out[name] = {
                        k: pool[name][k].at[sid].set(
                            self._jnp.take(snap[name][k], lane, axis=0
                                           ).astype(pool[name][k].dtype))
                        for k in pool[name]}
                return out

            fn = self._jit_cache["store_state"] = self._jax.jit(
                put, donate_argnums=(0,))
        self.pool = fn(self.pool, snap, self._jnp.asarray(lane, self._jnp.int32),
                       self.snap_arg(sid))

    def snap_arg(self, value: int):
        """A snapshot id or end position as the pool programs take it:
        a device scalar, made once for the 0 that every call of a stack
        without window layers passes (no transfer a commit there)."""
        if not value:
            return self._zero
        return self._jnp.asarray(value, self._jnp.int32)

    def _gather_fn(self, n: int):
        key = ("gather", n)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn
        layers = self._layers

        ring_layers = self._ring

        def gather(pool, block_ids, snap_ids):
            return {name: {ax: pool[name][ax][
                snap_ids if name in ring_layers or name in self._state
                else block_ids]
                for ax in pool[name]} for name in layers}

        from jax.sharding import PartitionSpec as P

        fn = self._pool_jit(gather, (self._pool_specs(), P(), P()))
        self._jit_cache[key] = fn
        return fn

    # -- migration wire format ----------------------------------------------
    def export_chain(self, chain: list[_Node]) -> tuple[dict, bytes]:
        """(meta, blob) for one chain: per layer (sorted), the k blocks
        then the v blocks, raw ``tobytes()`` concatenated; for a window
        layer the tail's one snapshot in their place.  ``meta`` carries
        what the importer must agree on; tokens travel beside it (the
        chain IS the token sequence).  With window layers the chain
        must end at a snapshot-bearing node (``reusable``)."""
        jnp = self._jnp
        if self._snapped and not (chain and chain[-1].snap):
            raise ValueError("chain tail owns no layer-state snapshot")
        ids = jnp.asarray([nd.block_id for nd in chain], jnp.int32)
        snaps = jnp.asarray([chain[-1].snap if self._snapped else 0],
                            jnp.int32)
        got = self._gather_fn(len(chain))(self.pool, ids, snaps)
        parts: list[bytes] = []
        for name in self._layers:
            for ax in self._axes(name):
                parts.append(np.asarray(got[name][ax]).tobytes())
        blob = b"".join(parts)
        meta = {"block": self.block, "n": len(chain),
                "layers": list(self._layers),
                "window": self.window, "ring_layers": sorted(self._ring),
                "layout": {name: [hk, d, str(np.dtype(dtype))]
                           for name, (hk, d, dtype) in self._layout.items()}}
        if self._latent:
            meta["latent_layers"] = sorted(self._latent)
            meta["layout"].update(
                {name: [row, str(np.dtype(dtype))]
                 for name, (_, row, dtype) in self._latent_layout.items()})
        if self._state:
            meta["state_layers"] = sorted(self._state)
            meta["layout"].update(
                {name: {k: [list(shape), str(np.dtype(dtype))]
                        for k, (shape, dtype) in leaves.items()}
                 for name, leaves in self._state_layout.items()})
        return meta, blob

    def _axes(self, name: str) -> tuple:
        """A layer's pool buffers in the blob's order."""
        return (tuple(self._state_layout[name]) if name in self._state
                else ("c",) if name in self._latent else ("k", "v"))

    def _wire_shapes(self, name: str, n: int) -> dict:
        """``{buffer: (shape, dtype)}`` of what a chain of ``n`` blocks
        carries for layer ``name``: n blocks of a global layer, one
        snapshot of a window or a state layer, n blocks of rows of a
        latent layer."""
        if name in self._state:
            return {k: ((1,) + shape, dtype)
                    for k, (shape, dtype) in self._state_layout[name].items()}
        if name in self._latent:
            _, row, dtype = self._latent_layout[name]
            return {"c": ((n, self.block, row), dtype)}
        hk, d, dtype = self._layout[name]
        m, t = (1, self.window) if name in self._ring else (n, self.block)
        return {"k": ((m, hk, d, t), dtype), "v": ((m, hk, t, d), dtype)}

    def import_chain(self, session: str, tokens: list[int], meta: dict,
                     blob: bytes) -> int:
        """Adopt a migrated chain: dedup against blocks already
        committed here, allocate + upload the rest, pin ``session`` at
        the tail.  Returns the number of blocks newly uploaded.  A pool
        too full to hold the whole chain truncates the import (the
        session resumes from the shorter prefix — still warmer than a
        cold start)."""
        jnp = self._jnp
        n = int(meta["n"])
        if int(meta["block"]) != self.block:
            raise ValueError(
                f"kv import block size {meta['block']} != local "
                f"{self.block}")
        if list(meta["layers"]) != self._layers:
            raise ValueError("kv import layer set mismatch")
        if (int(meta.get("window", 0)) != self.window
                or list(meta.get("ring_layers", [])) != sorted(self._ring)):
            raise ValueError("kv import window layers mismatch")
        if list(meta.get("state_layers", [])) != sorted(self._state):
            raise ValueError("kv import state layers mismatch")
        if list(meta.get("latent_layers", [])) != sorted(self._latent):
            raise ValueError("kv import latent layers mismatch")
        for name, (_, row, dtype) in self._latent_layout.items():
            if list(meta["layout"][name]) != [row, str(np.dtype(dtype))]:
                raise ValueError(f"kv import layout mismatch at {name}")
        for name, (hk, d, dtype) in self._layout.items():
            if list(meta["layout"][name]) != [hk, d,
                                              str(np.dtype(dtype))]:
                raise ValueError(f"kv import layout mismatch at {name}")
        for name, leaves in self._state_layout.items():
            if meta["layout"][name] != {
                    k: [list(shape), str(np.dtype(dtype))]
                    for k, (shape, dtype) in leaves.items()}:
                raise ValueError(f"kv import layout mismatch at {name}")
        if len(tokens) < n * self.block:
            raise ValueError(
                f"kv import: {len(tokens)} tokens cannot cover "
                f"{n} blocks of {self.block}")
        # slice the blob back into per-layer [n, ...] block arrays
        arrays: dict[str, dict[str, np.ndarray]] = {}
        off = 0
        for name in self._layers:
            arrays[name] = {}
            for ax, (shape, dtype) in self._wire_shapes(name, n).items():
                count = int(np.prod(shape, dtype=np.int64))
                if off + count * np.dtype(dtype).itemsize > len(blob):
                    raise ValueError(
                        f"kv import blob is {len(blob)} bytes, too short "
                        f"for the layout")
                arrays[name][ax] = np.frombuffer(
                    blob, dtype, count=count, offset=off).reshape(shape)
                off += count * np.dtype(dtype).itemsize
        if off != len(blob):
            raise ValueError(
                f"kv import blob is {len(blob)} bytes, layout needs {off}")
        node = self._root
        fresh: list[tuple[int, int]] = []      # (chain idx, block id)
        depth = 0
        for i, chunk in enumerate(self._chunks(tokens, n)):
            child = node.children.get(chunk)
            if child is None:
                child = self._extend(node, chunk)
                if child is None:
                    break
                fresh.append((i, child.block_id))
            else:                       # dedup walk touches the chain
                self._clock += 1
                child.last_use = self._clock
            node = child
            depth += 1
        # the snapshot belongs to the exported tail: a walk the pool cut
        # short ends elsewhere and resumes without it (a cold start)
        snap = self.snap_for(node) if depth == n else 0
        if fresh or snap:
            idx = [i for i, _ in fresh]
            ids = jnp.asarray([b for _, b in fresh], jnp.int32)
            snaps = jnp.asarray([snap], jnp.int32)
            snapped = self._ring | self._state
            upload = {
                name: {ax: jnp.asarray(a if name in snapped else a[idx])
                       for ax, a in arrays[name].items()}
                for name in self._layers}

            def put(pool, ids, snaps, upload):
                return {name: {ax: pool[name][ax].at[
                    snaps if name in snapped else ids].set(
                        upload[name][ax]) for ax in pool[name]}
                    for name in self._layers}

            key = ("import", len(fresh))
            fn = self._jit_cache.get(key)
            if fn is None:
                from jax.sharding import PartitionSpec as P

                # the upload shards like the pool (jit reshards the
                # host arrays on the way in), so each shard writes only
                # ITS heads of every fresh block — shape-aligned with
                # its pool slice by construction
                fn = self._pool_jit(
                    put, (self._pool_specs(), P(), P(), self._pool_specs()),
                    donate=(0,))
                self._jit_cache[key] = fn
            self.pool = fn(self.pool, ids, snaps, upload)
        if node is self._root:
            # a pool too full for even the FIRST block adopted nothing:
            # raising lets the exporter try the next candidate instead
            # of pinning the session to a replica with no chain
            raise RuntimeError(
                "kv import adopted zero blocks (pool exhausted by "
                "pinned/unevictable chains)")
        self.pin_session(session, node)
        return len(fresh)
