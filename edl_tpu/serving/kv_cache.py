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
  (``ContinuousBatcher._cache_shardings``): every shard holds the SAME
  block ids for ITS heads, so the one host-side trie indexes all
  shards at once and block identity stays a host concept.  The
  gather/scatter/import jits lift through ``shard_map`` so every
  block move is shard-local by construction — no collective can
  appear in the pool path (doc/serving.md "Mesh-sharded paged KV").

- **cache classes** - what a layer keeps for a slot and whether it
  pages by blocks or by **snapshots** is its
  :class:`~edl_tpu.serving.cache_layout.CacheClass`, handed in as
  ``classes``; this module names no kind.  Snapshots live in a pool of
  their own (``n_snaps`` entries, LRU among unpinned holders; entry 0 is
  scratch), each owned by the chain node it ends at: one entry holds
  every snapshotted layer's part.  A chain is reusable as deep as its
  deepest snapshot-bearing node (``match`` truncates to it).  One trie,
  one LRU clock, one ``store_blocks`` dispatch for every class; a chain
  exports each layer's blocks or its tail's snapshot, in layer order.

Thread model: single-writer — every mutating call runs on the engine
thread (admission, finish-commit, import-task); ``export_chain`` runs
only after the engine thread has stopped.  Counters are plain ints read
racily by ``stats()`` (atomic loads; exactness there is not a contract).
"""

from __future__ import annotations

import heapq
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from edl_tpu.obs.ledger import PROGRAM_BUILDS
from edl_tpu.serving.cache_layout import ROWS, cache_specs
from edl_tpu.utils.logger import get_logger

logger = get_logger(__name__)

# the pool programs' names in the program-build ledger, where the memo's
# key says less: one commit program a block count, the state snapshot
_BUILD_FAMILY = {"scatter": "pool_commit", "store_state": "snapshot"}


def pool_device_bytes(cache_shapes, block: int, n_blocks: int,
                      tp: int = 1, classes=None, n_snaps: int = 0) -> int:
    """Per-device HBM bytes of the pool :class:`PagedKVCache` would
    allocate for this cache skeleton under ``classes`` (``{layer:
    CacheClass}``; a layer it does not name keeps per-head rows):
    ``n_blocks`` blocks of ``block`` tokens for a layer paged by blocks,
    ``n_snaps`` snapshots for one paged by snapshots, split over ``tp``
    where the class shards, as the constructor shards them.  Plain
    element counts: libtpu lays the 16-token minor dim of a K block out
    major-most rather than padding it to 128 lanes (measured on v5e:
    device bytes / nominal = 1.00 for both buffers, f32 and bf16)."""
    total = 0
    for name, node in cache_shapes.items():
        cls = (classes or {}).get(name, ROWS)
        total += sum(
            int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
            for shape, dtype in cls.pool_shapes(
                node, block, n_blocks, n_snaps).values()
        ) // (tp if cls.sharded(node, tp) else 1)
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
    """Device block and snapshot pools (each layer's buffers as its
    cache class lays them out) plus the host-side prefix trie, free
    list, session pins and eviction policy.

    ``cache_shapes`` is the engine's per-slot cache skeleton
    (``{layer: what its mixer keeps}`` eval_shape tree) — pool layouts
    are derived from it so the gather/scatter jits line up with the slot
    slabs by construction.  ``classes`` is ``{layer: CacheClass}``
    (``cache_layout.cache_classes``); a layer it does not name keeps
    per-head key and value rows.

    ``mesh`` (optional) shards the pool buffers over the mesh's ``tp``
    axis on the KV-head dim, mirroring the engine's slot-slab sharding
    predicate per layer — every shard keeps the same block indices, so
    the host trie / free list / pins need no changes at all.
    """

    def __init__(self, cache_shapes, block: int, n_blocks: int,
                 max_sessions: int, mesh=None, classes=None,
                 n_snaps: int = 0):
        if block < 1:
            raise ValueError(f"kv block size must be >= 1, got {block}")
        if n_blocks < 1:
            raise ValueError(f"kv pool needs >= 1 block, got {n_blocks}")
        self.block = int(block)
        self.n_blocks = int(n_blocks)
        self._layers: list[str] = sorted(cache_shapes)
        self._shapes = cache_shapes
        self._cls = {name: (classes or {}).get(name, ROWS)
                     for name in self._layers}
        kinds = set(self._cls.values())
        self._snapped = any(c.snapshotted for c in kinds)
        # some class's snapshot comes out of the prefill alone: it sits
        # where a chain's last PROMPT ended (pin_session)
        self._prompt_snaps = any(c.snapshotted and not c.from_slot
                                 for c in kinds)
        self.window = max(c.window for c in kinds)
        self.n_snaps = int(n_snaps) if self._snapped else 0
        if self._snapped and self.n_snaps < 2:
            raise ValueError(
                f"layers that page by snapshots need at least 2 of them, "
                f"got {n_snaps}")
        for cls in kinds:
            if mesh is not None and (cls.no_shard or cls.snapshotted):
                raise ValueError(
                    f"a paged KV cache with {cls.kind} layers is not "
                    f"sharded over a mesh: " + (cls.no_shard or "the "
                    "snapshot pool has no sharded gather yet"))
        self._mesh = mesh
        self._tp = dict(mesh.shape).get("tp", 1) if mesh is not None else 1
        # block 0 is a reserved scratch block (never allocated) so a
        # zero-filled block-id vector can never alias live state
        self.pool = {}
        for name, cls in self._cls.items():
            try:
                shapes = cls.pool_shapes(cache_shapes[name], self.block,
                                         self.n_blocks, self.n_snaps)
            except ValueError as e:
                raise ValueError(f"layer {name}: {e}") from e
            self.pool[name] = {ax: jnp.zeros(shape, dtype)
                               for ax, (shape, dtype) in shapes.items()}
        if mesh is not None:
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
    def snap_alloc(self, session: str | None = None) -> int:
        """A free snapshot entry for the caller to write
        (``store_blocks``) and then ``snap_attach`` or ``snap_release``:
        0 when no layer keeps snapshots or every entry belongs to a
        pinned chain (counted).  A turn of ``session`` takes the entry
        of the session's OWN last prompt snapshot (where nothing else
        pins it; the turn is about to write a deeper one) when the
        entries are short: before a free one where the free entries are
        fewer than the pinned sessions, each of which asks for one more
        on its next turn, and in any case before it is refused.  One
        entry a session at a time is what lets six forty-turn sessions
        live on eight entries: with an entry a pinned session and one
        more a live turn, a session whose first turn found them all
        pinned went without for good and re-prefilled its whole context
        every turn (PERF.md section 6, PR 52)."""
        if not self._snapped:
            return 0
        own = self._session_snaps.get(session)
        if own is not None and own.pins != 1:
            own = None
        victim = None
        if own is None or len(self._snap_free) >= len(self._sessions):
            if self._snap_free:
                return self._snap_free.pop()
            victim = next((nd for nd in self._snap_lru if not nd.pins), None)
        if victim is None:
            if own is None:
                self.snap_skips += 1
                return 0
            self._unpin(self._session_snaps.pop(session))
            victim = own
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
        # a snapshot that only a prefill can take sits where the
        # chain's last PROMPT ended, above the tail: the session holds
        # that too
        holder = node
        while (self._prompt_snaps and holder is not None
               and not holder.snap):
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
    @property
    def block_bytes(self) -> int:
        """Bytes one block holds over every layer paged by blocks."""
        return sum(int(np.prod(buf.shape[1:])) * buf.dtype.itemsize
                   for name, node in self.pool.items()
                   if not self._cls[name].snapshotted
                   for buf in node.values())

    def blocks_used(self) -> int:
        return self.n_blocks - 1 - len(self._free)

    def blocks_free(self) -> int:
        return len(self._free)

    # -- mesh sharding -------------------------------------------------------
    def _pool_specs(self):
        """Per-layer PartitionSpec tree for the pool's buffers — the
        shard_map in/out specs and the constructor's device_put.
        Blocks stay whole on every shard (axis 0 unsharded); only the
        KV-head axis splits, exactly when the engine shards that
        layer's slot slabs (``CacheClass.sharded``): per-shard pools
        with IDENTICAL block ids, so a block move never crosses shards
        and one host trie covers every shard."""
        return {name: dict.fromkeys(
                    self.pool[name],
                    P(None, "tp") if self._cls[name].sharded(
                        self._shapes[name], self._tp) else P())
                for name in self._layers}

    def _pool_jit(self, key, fn, in_specs, donate=()):
        """jit ``fn`` over pool-shaped operands, once a ``key``; on a
        mesh, lift it through ``shard_map`` first so every block move is
        shard-local by construction (per-shard pools, identical indices
        — the body can never emit a collective).  ``in_specs`` is made
        on a mesh alone.  ``check_vma=False``: the bodies are all
        gathers/scatters by replicated indices, which the replication
        checker cannot prove through.  A program's first call is its
        span ``build/kv/<family>`` in the program-build ledger
        (``obs/ledger.py``); the memo then holds the bare jitted
        function."""
        jit = self._jit_cache.get(key)
        if jit is None:
            if self._mesh is not None:
                fn = jax.shard_map(
                    fn, mesh=self._mesh, in_specs=in_specs(),
                    out_specs=self._pool_specs(), check_vma=False)
            name = key if isinstance(key, str) else key[0]
            jit = self._jit_cache[key] = PROGRAM_BUILDS.first_call(
                jax.jit(fn, donate_argnums=donate), "kv",
                _BUILD_FAMILY.get(name, name), key, self._jit_cache, key)
        return jit

    # -- jitted device ops ---------------------------------------------------
    def load_prefix_into(self, cache, pool, block_ids, n: int, prefix_len,
                         snap_id=0):
        """Pure helper traced INSIDE the engine's reuse-prefill jit
        (``pool`` is the traced argument — never read device state off
        ``self`` under a trace): a fresh one-lane cache with a hit's
        prefix in it and its index at the traced ``prefix_len``, each
        layer as its class loads it (``CacheClass.load``): ``n``
        (padded) blocks at the front of a slab (``prefix_len <= n *
        block``; the scratch-padded tail lands beyond it and is
        overwritten or masked before any query can attend it), or
        snapshot ``snap_id`` (the chain tail's): the last window of the
        prefix back at its ring slots, a recurrence as it stood after
        token ``prefix_len - 1``."""
        return {name: self._cls[name].load(
                    cache[name], pool[name], block_ids, n, self.block,
                    prefix_len, snap_id)
                for name in self._layers}

    def _scatter_fn(self, n: int):
        """jit per new-block count: copy ``n`` contiguous blocks of one
        slot's slab (starting at traced byte position ``start``) into
        the pool at ``block_ids``, and the snapshot that ends at
        ``snap_end`` into entry ``snap_id``, each layer as its class
        stores it (``CacheClass.store``).  The pool is donated —
        committing never copies it."""
        def scatter(pool, cache, slot, start, block_ids, snap_id, snap_end):
            return {name: self._cls[name].store(
                        pool[name], cache[name], slot, start, block_ids, n,
                        self.block, snap_id, snap_end)
                    for name in self._layers}

        return self._pool_jit(
            ("scatter", n), scatter, lambda: (
                self._pool_specs(),
                cache_specs(self._cls, self._shapes, self._tp), *[P()] * 5),
            donate=(0,))

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
        def put(pool, snap, lane, sid):
            out = dict(pool)
            for name in snap:
                out[name] = {
                    k: pool[name][k].at[sid].set(
                        jnp.take(snap[name][k], lane, axis=0).astype(
                            pool[name][k].dtype))
                    for k in pool[name]}
            return out

        # no mesh pages a class that snapshots (the constructor refuses)
        self.pool = self._pool_jit("store_state", put, None, donate=(0,))(
            self.pool, snap, jnp.asarray(lane, jnp.int32),
            self.snap_arg(sid))

    def snap_arg(self, value: int):
        """A snapshot id or end position as the pool programs take it:
        a device scalar, made once for the 0 that every call of a stack
        without window layers passes (no transfer a commit there)."""
        if not value:
            return self._zero
        return jnp.asarray(value, jnp.int32)

    def _gather_fn(self, n: int):
        def gather(pool, block_ids, snap_ids):
            return {name: {ax: buf[snap_ids if self._cls[name].snapshotted
                                   else block_ids]
                           for ax, buf in pool[name].items()}
                    for name in self._layers}

        return self._pool_jit(("gather", n), gather, lambda: (
            self._pool_specs(), P(), P()))

    # -- migration wire format ----------------------------------------------
    def _grouped(self) -> dict:
        """``{metadata key: its layers, sorted}`` of the classes that
        have one; ``ring_layers`` always (an older importer reads it)."""
        out = {"ring_layers": []}
        for name in self._layers:
            if self._cls[name].meta_key:
                out.setdefault(self._cls[name].meta_key, []).append(name)
        return out

    def export_chain(self, chain: list[_Node]) -> tuple[dict, bytes]:
        """(meta, blob) for one chain: per layer (sorted), its pool
        buffers in order (a layer paged by blocks: the chain's blocks,
        k then v; one paged by snapshots: the tail's one snapshot), raw
        ``tobytes()`` concatenated.  ``meta`` carries what the importer
        must agree on; tokens travel beside it (the chain IS the token
        sequence).  With snapshotted layers the chain must end at a
        snapshot-bearing node (``reusable``)."""
        if self._snapped and not (chain and chain[-1].snap):
            raise ValueError("chain tail owns no layer-state snapshot")
        ids = jnp.asarray([nd.block_id for nd in chain], jnp.int32)
        snaps = jnp.asarray([chain[-1].snap if self._snapped else 0],
                            jnp.int32)
        got = self._gather_fn(len(chain))(self.pool, ids, snaps)
        blob = b"".join(np.asarray(got[name][ax]).tobytes()
                        for name in self._layers for ax in self.pool[name])
        groups = self._grouped()
        meta = {"block": self.block, "n": len(chain),
                "layers": list(self._layers), "window": self.window,
                "ring_layers": groups.pop("ring_layers"),
                "layout": {name: self._cls[name].meta(self._shapes[name])
                           for name in self._layers},
                **dict(sorted(groups.items()))}
        return meta, blob

    def import_chain(self, session: str, tokens: list[int], meta: dict,
                     blob: bytes) -> int:
        """Adopt a migrated chain: dedup against blocks already
        committed here, allocate + upload the rest, pin ``session`` at
        the tail.  Returns the number of blocks newly uploaded.  A pool
        too full to hold the whole chain truncates the import (the
        session resumes from the shorter prefix — still warmer than a
        cold start)."""
        n = int(meta["n"])
        if int(meta["block"]) != self.block:
            raise ValueError(
                f"kv import block size {meta['block']} != local "
                f"{self.block}")
        if list(meta["layers"]) != self._layers:
            raise ValueError("kv import layer set mismatch")
        groups = self._grouped()
        if int(meta.get("window", 0)) != self.window:
            raise ValueError("kv import window layers mismatch")
        for key in sorted({*groups, *(k for k in meta
                                      if k.endswith("_layers"))}):
            mine = groups.get(key, [])
            if list(meta.get(key, [])) != mine:
                kind = (self._cls[mine[0]].kind if mine
                        else key[:-len("_layers")])
                raise ValueError(f"kv import {kind} layers mismatch")
        for name in self._layers:
            got = meta["layout"][name]
            if (list(got) if isinstance(got, tuple) else got) != \
                    self._cls[name].meta(self._shapes[name]):
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
            # what a chain of n blocks carries: n blocks, or one snapshot
            for ax, (shape, dtype) in self._cls[name].pool_shapes(
                    self._shapes[name], self.block, n, 1).items():
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
            snapped = {name for name in self._layers
                       if self._cls[name].snapshotted}
            upload = {
                name: {ax: jnp.asarray(a if name in snapped else a[idx])
                       for ax, a in arrays[name].items()}
                for name in self._layers}

            def put(pool, ids, snaps, upload):
                return {name: {ax: pool[name][ax].at[
                    snaps if name in snapped else ids].set(
                        upload[name][ax]) for ax in pool[name]}
                    for name in self._layers}

            # the upload shards like the pool (jit reshards the host
            # arrays on the way in), so each shard writes only ITS heads
            # of every fresh block — shape-aligned with its pool slice
            # by construction
            self.pool = self._pool_jit(
                ("import", len(fresh)), put, lambda: (
                    self._pool_specs(), P(), P(), self._pool_specs()),
                donate=(0,))(self.pool, ids, snaps, upload)
        if node is self._root:
            # a pool too full for even the FIRST block adopted nothing:
            # raising lets the exporter try the next candidate instead
            # of pinning the session to a replica with no chain
            raise RuntimeError(
                "kv import adopted zero blocks (pool exhausted by "
                "pinned/unevictable chains)")
        self.pin_session(session, node)
        return len(fresh)
