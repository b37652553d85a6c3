"""Slot-based continuous batching for TransformerLM decode.

The reference's serving story is batch-at-a-time classification
(Paddle Serving teachers, distill_worker.py:197-321); an LM server
that pads every request into one fixed batch wastes the chip whenever
requests arrive raggedly or finish early.  This engine keeps a fixed
pool of ``slots`` decode lanes over ONE persistent KV cache:

- a new request **prefills** into any free slot (per-prompt-length
  bucket, compiled once per bucket; buckets extend by doubling up to
  the cache length, so any prompt that leaves room for one generated
  token is accepted);
- every decode dispatch advances ALL slots ``steps_per_sync`` tokens
  under one jitted ``lax.scan``, and the host reads its results ONE
  TICK LATE: tick n+1's programs are enqueued before tick n is read (a
  one-tick lookahead, ``_tick``), so the device runs program after
  program while the host reads, books and admits.  The step's last
  tokens stay on the device as the next step's input, and the host
  schedules from budgets (``max_new`` and the count of programs
  enqueued), so the sync cadence no longer sets a floor under the
  tick: ``steps_per_sync`` is how often the host looks (finish and
  admission granularity), not what the device waits for;
- prefill work is **bounded and overlapped**: each engine tick
  dispatches at most ONE prefill group (so a burst of arrivals can never
  starve running lanes), then the decode chunk, then the insert — and
  syncs the host ONCE for all of it, a tick later.  Active lanes advance
  ``steps_per_sync`` tokens every tick no matter how fast requests
  arrive; ``stats()['prefill_stall_s']`` bounds the decode wall-time
  cost of prefill dispatches: it is the host time of ``engine/admit``
  (below) in those ticks where lanes were live AND something was
  admitted — a part of ``tick_admit_s``, never more than it;
- **chunked prefill** (``prefill_chunk``, ISSUE 20): an admission whose
  prompt exceeds the chunk size prefills into a private one-lane slab
  ONE chunk per tick, interleaved with the decode dispatches, so an
  8k-token prompt costs live lanes one chunk of stall per tick instead
  of one monolithic prefill — the final chunk rides the shared
  insert/finish path like any other admission.  The lane holds up to
  TWO such admissions (ISSUE 54), each with its own slab and at its own
  offset: a tick's token steps then carry two chunks where they carried
  one, and the second joins whenever it reaches the queue's front;
- **speculative decoding** (``spec_k`` + a draft model, ISSUE 20): each
  tick runs draft-k/verify-once rounds — the draft proposes k tokens
  per slot, the target checks all k+1 positions in ONE multi-token
  pass, and greedy acceptance (token == the target's argmax) keeps the
  emitted stream bit-identical to plain decode while consuming up to
  k+1 tokens per target dispatch;
- a finished slot (token budget or ``eos_id``) frees when the host
  reads its last token and the next queued request takes it in the
  next tick — no convoy behind the longest generation in a batch;
- **generation by diffusion over blocks** (``cfg.block_length = L >
  0``): a dispatch is ``steps_per_sync`` PASSES (``_pass_impl``), not
  token steps.  A slot's open block (``L`` tokens and a masked flag a
  position, on the device) goes through the model whole each pass, over
  the slot's committed rows; a pass with a masked position unmasks some
  (a denoise pass, whose K/V no later pass reads), a pass with none
  COMMITS: the index moves ``L`` rows, the block's tokens are the
  pass's output, and the same forward (``2 L`` rows a slot, a commit
  half and an open half) opens the next block and unmasks its first
  position(s).  So a pass yields 0 or ``L`` tokens a slot
  (``_finish_blocks`` reads them raggedly), prompts prefill
  ``floor(P / L) * L`` rows under the block-causal mask and hand their
  tail to the first block, and rows enter the pool at commit only
  (doc/serving.md, "Block passes").

Per-slot independence rests on the transformer's per-example
``cache_index`` contract (transformer.Block._decode_attention): each
slot's position/mask advances alone, so a slot mid-generation is
bit-identical to the same request decoded in isolation (the greedy
parity test in tests/test_serving_engine.py asserts exactly that).

Thread model: callers ``submit()`` from any thread and get a Future;
one engine thread owns the device state — the same
single-writer/many-readers split as the TeacherServer coalescer.

**Where a tick's time goes** (the tick ledger, an
:class:`edl_tpu.obs.ledger.StepPhaseLedger` on the engine thread).
The phases tile ``_loop``: ``idle_wait`` (blocked in ``_drain`` with no
live slot and no chunk in flight: waiting for requests; time BETWEEN
ticks), then inside a tick ``tasks`` (closures from other threads),
``admit`` (queue drain, prefix matching, prefill/chunk dispatches),
``dispatch`` (the decode step or speculative round and the inserts),
``sync`` (the one ``device_get`` of the PREVIOUS tick's programs:
blocked on the device, which meanwhile holds this tick's), ``finish``
(that tick's tokens to slots and futures) and, nested in it and
deducted from it, ``kv_commit``.  ``lookahead_ticks`` counts the ticks
that enqueued a step or an insert behind an unread tick (a tick that
only advanced a chunked admission has nothing to read and is not one),
``lookahead_discarded_token_steps`` the token steps run for a slot
after an EOS the host had not read.
Each phase is exclusive host seconds in ``stats()``
(``tick_<phase>_s``, ``idle_wait_s``, ``ticks``, ``tick_s``,
``tick_coverage``) and in ``edl_engine_tick_phase_seconds{phase}``, and
an ``engine/<phase>`` span in any profiler capture (``engine/admit``
and ``engine/dispatch`` carry ``pending``, ``cause``, ``lane_offset``
and ``ahead`` as the span's arguments).

**Why a request waited** (the request-stage ledger, an
:class:`edl_tpu.obs.ledger.RequestStageLedger`).  A request's life is
three stages end to end, each closed where the engine thread makes the
transition: ``queue_wait`` (``submit()`` until it leaves ``_pending``
for an admission), ``prefill`` (until its first token is read on the
host; for a long prompt the whole time it held the chunk lane) and
``decode`` (until its future resolves); ``ttft`` is the first two
together and ``deliver`` what the replica adds after that (the answer's
way out, ``ReplicaServer.serve_release`` through :meth:`ContinuousBatcher.
observe_stage`).  Every request is admitted down one of three LANES:
``cold`` (a same-bucket group prefilled in one program), ``chunk`` (a
prompt over ``prefill_chunk`` tokens, one chunk a tick, two prompts at
most at a time) or ``reuse`` (a prefix hit: the suffix alone).  While it
waits it is charged, tick by tick, to the CAUSE that kept the queue's
head where it was: ``slots`` (no free slot), ``lane`` (a slot was free
but the chunk lane was held, which takes the tick's one cold group and,
once full, bars the next long prompt), ``group`` (the tick's one cold
group took another bucket or its lane cap) or ``tick`` (it arrived after
the tick's admission ran: the granularity of the loop itself).  The four
sum to the queue wait, per request and in ``stats()``.  All of it is in
``stats()`` as flat cumulative keys (``stage_<stage>_sum_s`` / ``_n``,
the same by lane for ``queue_wait`` and ``prefill``, cumulative bucket
counts ``stage_<stage>_le_<edge>`` on one geometric ladder for
``queue_wait`` and ``ttft``, ``queue_wait_cause_<cause>_s``) beside the
pairs that were there (``queue_wait_s_sum``/``admitted``,
``ttft_s_sum``/``first_tokens``, ``decode_s_sum``/``decode_tokens``), in
the ``edl_engine_queue_wait_seconds`` / ``edl_engine_ttft_seconds`` /
``edl_engine_intertoken_seconds`` histograms, and on one
``engine/request`` trace event under the submitter's trace.  Beside
them the device's queue as the host knows it (``device_enqueues``,
``device_queue_programs_sum``: programs enqueued and not yet proven
run by a read, summed at every enqueue) and the chunk lane's
occupancy (``chunk_lane_busy_s``: the time it held one admission or
two).  There is no switch.
"""

from __future__ import annotations

import dataclasses
import functools
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from edl_tpu.models.generate import (_split_layer_params, sample_logits,
                                     sown_layout, sown_vector)
from edl_tpu.models.transformer import TransformerConfig, TransformerLM
from edl_tpu.obs import context as obs_context
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.obs.ledger import (PROGRAM_BUILDS, RequestStageLedger,
                                StepPhaseLedger)
from edl_tpu.serving import cache_layout, model_counters
from edl_tpu.serving.kv_cache import PagedKVCache, pool_device_bytes
from edl_tpu.utils import constants
from edl_tpu.utils.logger import get_logger

logger = get_logger(__name__)

DEFAULT_PREFILL_BUCKETS = (32, 64, 128, 256, 512)

# a block engine's own counters (stats(), cumulative): pass programs'
# iterations dispatched; (slot, pass) pairs that were live in them, the
# blocks they committed (the commit passes, a slot each) and the
# positions they unmasked, as the programs counted them; the first
# blocks' positions that prompts gave; tokens the commits delivered to
# requests (blocks x L less the given positions and the cut ends).  The
# rows the live pairs attended (a pair's committed rows and its block)
# are ``decode_kv_tokens_live``, as a token step's are
_BLOCK_KEYS = ("blockdiff_passes", "blockdiff_slot_passes",
               "blockdiff_blocks_committed", "blockdiff_commits_fused",
               "blockdiff_tokens_unmasked",
               "blockdiff_given_tokens", "blockdiff_tokens_delivered")

# the engine thread's time, tiled (module docstring); idle_wait is the
# time between ticks, kv_commit nests in finish
TICK_PHASES = ("idle_wait", "tasks", "admit", "dispatch", "sync", "finish",
               "kv_commit")
# a tick is 1-100 ms and a decode token 2-30 ms: finer than the default
_FINE_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0)
_TICK_PHASE_SECONDS = obs_metrics.histogram(
    "edl_engine_tick_phase_seconds",
    "Per-tick exclusive host time of the engine thread by phase: "
    "idle_wait / tasks / admit / dispatch / sync / finish / kv_commit "
    "(serving/engine.py tick ledger)", ("phase",), buckets=_FINE_BUCKETS)
# a request's life (module docstring): the lane it was admitted down,
# the stages that tile it, each between two of its stamps, and with
# them ttft (the first two together) and deliver (the replica's); what
# kept it waiting.  The lane splits the stages where it decides the
# service time (a short prompt's wait against a long one's, one program
# against a lane held for seconds)
LANES = ("cold", "chunk", "reuse")
TILING_STAGES = ("queue_wait", "prefill", "decode")
_STAGE_STAMPS = {"queue_wait": ("t_submit", "t_admit"),
                 "prefill": ("t_admit", "t_first"),
                 "decode": ("t_first", "t_done"),
                 "ttft": ("t_submit", "t_first")}
_STAGE_LANES = {"queue_wait": LANES, "prefill": LANES, "decode": (),
                "ttft": (), "deliver": ()}
WAIT_CAUSES = ("slots", "lane", "group", "tick")
_QUEUE_WAIT_SECONDS = obs_metrics.histogram(
    "edl_engine_queue_wait_seconds",
    "submit() to admission (popped into a prefill, reuse or chunked "
    "admission), per request, as the engine sees it")
_TTFT_SECONDS = obs_metrics.histogram(
    "edl_engine_ttft_seconds",
    "submit() to the request's first token on the host, per request "
    "(engine-side time to first token; the client still receives whole "
    "answers)")
_INTERTOKEN_SECONDS = obs_metrics.histogram(
    "edl_engine_intertoken_seconds",
    "Mean gap between a finished request's tokens after the first: "
    "(done - first token) / (tokens - 1)", buckets=_FINE_BUCKETS)


def _zeros_of(shapes):
    """Zeros for a tree of ``ShapeDtypeStruct``s, traced or eager."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


# what a prefill program lets its layers write: the cache, what they
# count, and a recurrent layer's state at ``snap_at``
_PREFILL_MUTABLE = ["cache", "intermediates", "snap"]


def _compiled(family, key):
    """A program family: the method builds the program of its arguments,
    and the engine keeps one a ``key(*args)`` (``_prefill_cache``).  A
    program's first call is its span ``build/engine/<family>`` in the
    program-build ledger (``obs/ledger.py``); from then on the memo
    holds the bare jitted function."""
    def programs(build):
        def get(self, *args):
            k = key(*args)
            fn = self._prefill_cache.get(k)
            if fn is None:
                fn = self._prefill_cache[k] = PROGRAM_BUILDS.first_call(
                    build(self, *args), "engine", family, k,
                    self._prefill_cache, k)
            return fn
        return functools.wraps(build)(get)
    return programs


def _setup_span(init):
    """``ContinuousBatcher.__init__`` as ``setup/engine/state``: casts,
    the slot cache and the pool allocated (and whatever eager programs
    that takes)."""
    @functools.wraps(init)
    def construct(self, *args, **kwargs):
        with PROGRAM_BUILDS.setup("engine"):
            init(self, *args, **kwargs)
    return construct


@dataclass
class _Slot:
    request: "_Request | None" = None
    emitted: list[int] = dataclasses.field(default_factory=list)
    remaining: int = 0    # tokens the host has yet to READ
    # decode tokens no dispatched program covers yet: what the host
    # schedules from.  A slot is live in the next step iff owed > 0
    owed: int = 0
    # block passes: tokens of the committed blocks (whole: what the
    # slot's committed rows hold past the prefill), first-block
    # positions that were the prompt's, and masked positions of the open
    # block as the host schedules them (static remasking)
    blocks: list[int] = dataclasses.field(default_factory=list)
    skip: int = 0
    masked: int = 0
    # passes the slot sits out before its first (``_start_wait``): as
    # the host schedules them, and as it reads them
    wait: int = 0
    idle: int = 0

    @property
    def free(self) -> bool:
        return self.request is None


class _Request:
    __slots__ = ("ids", "max_new", "future", "session", "ctx", "skipped",
                 "snap", "cut", "t_submit", "t_admit", "t_first", "t_done",
                 "lane", "chunks", "cause", "t_mark", "waits", "given",
                 "n_first", "wait")

    def __init__(self, ids: np.ndarray, max_new: int,
                 session: str | None = None):
        self.ids = ids
        # block passes: the prompt's tail that opens the first block
        # (``ids`` is then the rows prefilled), and the tokens the first
        # read delivered (a token step's first token is one)
        self.given = ids[:0]
        self.n_first = 1
        self.wait = 0       # block passes: ``_start_wait``
        self.max_new = max_new
        self.session = session
        self.future: Future = Future()
        # the submitter's trace (the gateway's, through serve_submit):
        # the engine thread has none of its own
        self.ctx = obs_context.current()
        self.skipped = 0          # prompt tokens a prefix hit spared
        # window layers: the snapshot (id, end) taken when the prompt's
        # prefill ended, until the commit finds its node
        self.snap = (0, 0)
        # pooled tokens a hit had to give up and prefill again: no
        # layer-state snapshot that deep (kv_cache.match's last_cut)
        self.cut = 0
        # stages, on one monotonic clock: submit <= admit <= first <= done
        self.t_submit = time.monotonic()
        self.t_admit = self.t_first = self.t_done = None
        # the stage ledger's record of this request: the lane it was
        # admitted down and the chunks it took there, and its wait by
        # cause: charged to ``cause`` since ``t_mark`` (born waiting for
        # the tick's admission)
        self.lane = None
        self.chunks = 0
        self.cause, self.t_mark = "tick", self.t_submit
        self.waits: dict[str, float] = {}

    def stage_s(self, stage: str) -> float:
        """Seconds of a closed stage: the one place the stamps are
        subtracted (the ledger and the ``engine/request`` event both
        read this)."""
        begin, end = _STAGE_STAMPS[stage]
        return getattr(self, end) - getattr(self, begin)


@dataclass
class _ChunkState:
    """One chunked admission in flight: the request holds a claimed
    slot while its prompt prefills into a private one-lane slab, one
    chunk per tick (``ContinuousBatcher._advance_chunk``)."""

    req: "_Request"
    slot: int
    offset: int           # prompt tokens already prefilled
    slab: object          # one-lane decode cache, index == offset
    sown: object          # device counters accumulator (traced through)


@dataclass
class _Tick:
    """What one tick enqueued and the host has not read: the decode
    step's results with the (slot, request) pairs that were live in it,
    and the admissions inserted behind it (``_dispatch_prefill``'s
    tuples).  The requests are named because a slot can change hands
    before the read: an EOS frees it one read late."""

    live: list
    dec: object = None
    counters: object = None       # the step's sown_vector
    counts: object = None
    passes: object = None         # a pass program's own counts
    pres: list = dataclasses.field(default_factory=list)
    # programs enqueued up to the one this tick's read waits for: read,
    # they have all run (the device runs them in order)
    mark: int = 0


class _Task:
    """A closure the ENGINE THREAD runs between ticks (single-writer
    device mutations from other threads — e.g. a migrated-session KV
    import arriving over the wire — are serialised through the same
    queue the requests ride)."""

    __slots__ = ("fn", "future")

    def __init__(self, fn):
        self.fn = fn
        self.future: Future = Future()


class ContinuousBatcher:
    """``submit(prompt_1d) -> Future[np.ndarray]`` over a slot pool.

    ``cfg``/``params`` as for :func:`edl_tpu.models.generate.generate`
    (training config + trained params — layer stacking is split here).
    ``max_len`` bounds prompt+generation per slot (defaults to
    ``cfg.max_len``); the KV cache is [slots, ...] at that length.
    ``steps_per_sync`` is a latency dial: token steps a program, so
    how often the host reads (a request finishes, and a waiting one is
    admitted, at a program's end) against dispatches per token.  The
    device does not wait for those reads (``_tick``: the next program
    is enqueued before this one is read).  A slot whose budget ends
    inside a program wastes at most ``steps_per_sync - 1`` lane-steps;
    with ``eos_id`` set, an EOS costs up to one more program of them.

    ``mesh`` (optional) lifts the engine onto a device mesh: params are
    tp-sharded by their logical axes (models/generate.shard_split_params)
    and the KV cache is sharded over ``tp`` on the kv-head axis, so a
    model bigger than one chip's HBM serves from the same slot pool —
    the reference's teacher regime (a ResNeXt101 spanning its GPU,
    /root/reference/README.md:51-64).  The slot logic stays host-side
    and unchanged; XLA inserts the tp collectives from the shardings.
    Tokens match the unsharded engine exactly (greedy parity tested on
    a tp=2 mesh).  Mesh engines page too (ISSUE 20): the block pool
    shards over the same ``tp`` axis as the slot slabs with one
    host-side trie over all shards (kv_cache.PagedKVCache).

    ``prefill_chunk`` / ``spec_k`` are the serving fast-path knobs
    (module docstring); ``spec_k > 0`` needs ``draft_cfg`` +
    ``draft_params`` (a smaller model over the SAME vocabulary) and a
    greedy engine (``temperature <= 0``) — acceptance compares the
    draft against the target's argmax, which is what makes the output
    provably identical to plain decode.
    """

    @_setup_span
    def __init__(self, cfg: TransformerConfig, params, *, slots: int = 8,
                 max_len: int | None = None,
                 prefill_buckets: tuple[int, ...] = DEFAULT_PREFILL_BUCKETS,
                 temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 0.0, eos_id: int | None = None,
                 steps_per_sync: int = 8, rng_seed: int = 20_26,
                 mesh=None, rules=None, kv_block: int = 0,
                 kv_pool_blocks: int = 0, prefix_reuse: bool = True,
                 kv_max_sessions: int | None = None,
                 prefill_chunk: int | None = None,
                 spec_k: int | None = None,
                 draft_cfg: TransformerConfig | None = None,
                 draft_params=None):
        cache_len = max_len or cfg.max_len
        self.cfg = cfg
        self._T = max(1, steps_per_sync)
        # token steps a slot can run on as live after its request's
        # last token: the rest of the program its budget ends in and,
        # where an EOS can end it early, the one program the lookahead
        # had already enqueued when the host read the EOS (_tick)
        self._overrun = self._T - 1 + (self._T if eos_id is not None else 0)
        # what each layer keeps for a slot and how it pages (attention_impl
        # "dense" never reads the mesh; the decode step does,
        # ops/decode_attention.applies: a mesh engine's sharded slabs stay
        # on the einsum path)
        dcfg = dataclasses.replace(cfg, decode=True, attention_impl="dense",
                                   mesh=mesh, max_len=cache_len)
        self._classes = cache_layout.cache_classes(dcfg)
        # the distinct classes, in layer order
        kinds = self._kinds = list(dict.fromkeys(self._classes.values()))
        k = constants.SPEC_K if spec_k is None else int(spec_k)
        self._block = L = int(cfg.block_length)
        if L:
            self._init_block(L, k, mesh, cache_len, kv_block, prefill_chunk)
        for cls in kinds:
            if k > 0 and cls.no_rewind:
                raise ValueError(
                    f"speculative decoding (spec_k > 0) does not serve a "
                    f"{cls.noun} configuration: {cls.no_rewind}")
        for cls in kinds:
            if mesh is not None and cls.no_shard:
                raise ValueError(
                    f"a mesh engine does not serve a {cls.noun} "
                    f"configuration: {cls.no_shard} (serving/kv_cache.py)")
        # snapshot policy is the engine's, keyed on what the classes
        # say: some layer pages by snapshots; some snapshot can be read
        # out of a slot after the fact (a ring's); some only where a
        # prefill program is AT (a recurrent state's)
        snapped = [c for c in kinds if c.snapshotted]
        self._snapped = bool(snapped)
        self._ringed = any(c.from_slot for c in snapped)
        self._recurrent = any(not c.from_slot for c in snapped)
        # a slot of a window class holds a ring of the window and what a
        # snapshot for the pool reads back after the last token: up to
        # one KV block back to the block edge it ends at, and the
        # overrun.  A window that tiles by lanes keeps a ring that does
        # (the one-token kernels take it)
        ring, W = 0, max(c.window for c in kinds)
        if W:
            lanes = 128 if W % 128 == 0 else 1
            ring = -(-(W + max(kv_block, 1) + self._overrun)
                     // lanes) * lanes
        self._dcfg = dataclasses.replace(dcfg, window_ring=ring)
        self._model = TransformerLM(self._dcfg)
        # the pass model scatters a slot's block at the slot's own index
        # (TransformerConfig.pass_tokens)
        self._pmodel = (TransformerLM(dataclasses.replace(
            self._dcfg, decode_scatter=True)) if self._block else None)
        # the last-position cut: a stack whose last layers keep nothing
        # a position runs them at the one row a lane a multi-token
        # program samples at, and not at all in a chunk that samples
        # nothing (``TransformerLM``: ``last_at``, ``tail``)
        self._cut = self._dcfg.tail_start < self._dcfg.num_layers
        self._pending: "deque[_Request]" = deque()
        self._mesh = mesh
        # a replicated output's sharding; None off a mesh, where every
        # program's out_shardings are jax.jit's "unspecified"
        self._rep = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._rep = NamedSharding(mesh, PartitionSpec())
            from edl_tpu.models.generate import shard_split_params
            self._params = shard_split_params(params, mesh, cfg.num_layers,
                                              rules)
        else:
            self._params = _split_layer_params(params, cfg.num_layers)
        self._slots = [_Slot() for _ in range(slots)]
        # prefill sub-batch ladder: any group of waiting same-bucket
        # requests splits greedily into these sizes, so prefill
        # DISPATCHES amortise across requests instead of paying a host
        # round-trip each.  Scaled with the slot pool: a 64-slot engine
        # admits a 32-request burst in one dispatch where a fixed 8-cap
        # took four — dispatch count IS the admission cost on any host
        # (measured +23% engine tokens/s at 64 slots on v5e), and
        # compile count stays bounded at buckets × |ladder|.
        self.PREFILL_KS = (tuple(k for k in (32, 16, 8, 4, 2, 1)
                                 if k <= slots) or (1,))
        buckets = sorted(b for b in prefill_buckets if b <= cache_len)
        if not buckets:
            # every configured bucket exceeds the cache: one bucket at
            # the cache length still serves any prompt submit() accepts
            buckets = [cache_len]
        # extend by doubling to cache_len: the prompt cap is the CACHE,
        # not the configured bucket list (a 1024-cache engine must
        # accept a 600-token prompt even with default 512-max buckets)
        while buckets[-1] < cache_len:
            buckets.append(min(buckets[-1] * 2, cache_len))
        self._buckets = tuple(buckets)
        self._temperature = temperature
        self._top_k = top_k
        self._top_p = top_p
        self._eos = eos_id
        self._rng = jax.random.key(rng_seed)
        blocks_per_slot = max(1, cache_len // kv_block) if kv_block > 0 else 0
        pool_blocks = (kv_pool_blocks or (2 * slots * blocks_per_slot + 1)
                       if kv_block > 0 else 0)
        chunk = (constants.PREFILL_CHUNK if prefill_chunk is None
                 else prefill_chunk)
        self._chunk_tokens = max(0, int(chunk))
        # the cache's layout is fixed here: shape trees per lane count
        # (_cache_shapes) and every compiled program keyed by its shapes
        self._shape_memo: dict[str, object] = {}
        self._prefill_cache: dict[tuple, object] = {}
        sessions = (constants.KV_SESSIONS if kv_max_sessions is None
                    else kv_max_sessions)
        # window snapshots (kv_cache.py): a pinned tail a session, one
        # a finished request until it ages out, and the scratch entry
        # a state layer's snapshot is its whole slot state (38 MB at
        # granite-4.0-h-small's widths where a window's is 3): as many
        # of them as _require_fit finds room for, slots / 2 at least
        n_snaps = (sessions + 2 * slots + 1
                   if self._snapped and kv_block > 0 else 0)
        n_snaps = self._require_fit(slots, kv_block, pool_blocks, n_snaps)
        one_lane = self._cache_shapes(1)
        self._cache = self._fresh_cache(slots)
        # last token per slot, ON THE DEVICE: each step returns it, each
        # insert places an admission's first token in it, the next step
        # takes it.  The host never reads it (_tick)
        self._toks = (self._block_state(slots) if self._block
                      else jnp.zeros((slots,), jnp.int32))
        if mesh is not None:
            self._toks = jax.device_put(self._toks, self._rep)
        # -- paged KV block pool + prefix-reuse index (kv_cache.py) --
        # kv_block=0 keeps the engine EXACTLY on the pre-paged path (no
        # pool, no index, no extra dispatches); with a block size, every
        # finished request's full KV blocks persist in the pool and an
        # admission whose prompt extends a committed chain prefills only
        # the suffix.  On a mesh the pool shards with the slot slabs
        # (same tp axis, one host trie over all shards) — the pool jits
        # are shard_map'd inside PagedKVCache, so paging costs a mesh
        # engine no collectives.
        self._kv = None
        self._reuse = bool(prefix_reuse)
        if kv_block > 0:
            self._kv = PagedKVCache(
                one_lane, kv_block, pool_blocks, sessions, mesh=mesh,
                classes=self._classes, n_snaps=n_snaps)
        self._kv_hits = 0
        self._kv_misses = 0
        self._prefill_tokens = 0
        self._prefill_tokens_skipped = 0
        # -- chunked prefill (long admissions interleave with decode) --
        # the lane's admissions, ``_chunk_lanes()`` at most, each with
        # its own one-lane slab; ``_lane_t0`` is when the first of those
        # it holds entered it (monotonic)
        self._chunking: list[_ChunkState] = []
        self._lane_t0 = 0.0
        self._prefill_chunks = 0
        self._chunk_pair_dispatches = 0
        self._chunked_admissions = 0
        self._tasks: "deque[_Task]" = deque()
        self._queue: queue.Queue[_Request | _Task | None] = queue.Queue()
        self._stopping = False
        self._draining = False
        # makes check-stopping + enqueue atomic vs stop()'s drain (the
        # TeacherServer guard — without it a submit racing stop() can
        # land its request in the already-drained queue, stranding the
        # caller's future forever)
        self._enqueue_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._done_requests = 0
        self._submitted_requests = 0  # accepted submits (enqueue lock)
        self._failed_requests = 0     # futures failed while engine lives
        self._emitted_tokens = 0
        # what the model's layers count (model_counters.py): every
        # program returns one vector under this layout, read with the
        # tick's own sync
        self._counters = model_counters.ModelCounters(
            self._dcfg, self._classes, one_lane, slots,
            self._sown_layout(slots), self._stats_lock)
        self._acc_shape = jax.ShapeDtypeStruct((self._counters.width,),
                                               jnp.float32)
        slab0 = max(jax.tree.leaves(self._cache), key=lambda x: x.ndim)
        pool0 = (jax.tree.leaves(self._kv.pool)[0]
                 if self._kv is not None else None)
        logger.info(
            "kv cache: %d slots x %d tokens (%s, slab sharding %s); pool "
            "%d blocks of %d (sharding %s); layers by cache class %s (ring "
            "%d); %d layer-state snapshots; the layers sow %s",
            slots, cache_len, slab0.dtype.name,
            mesh and slab0.sharding.spec, pool_blocks, kv_block,
            mesh and pool0 is not None and pool0.sharding.spec,
            [c.kind for c in self._classes.values()], ring, n_snaps,
            self._counters.layout)
        self._lane_steps = 0          # slot-steps actually dispatched
        self._active_lane_steps = 0   # of those, slots with live requests
        # recurrent layers' prompt snapshots not taken, and pooled tokens
        # prefilled again for want of a snapshot
        self._state_snap_skips = 0
        self._state_reprefill = 0
        self._prefill_stall_s = 0.0   # prefill dispatch time w/ lanes live
        # the tick enqueued and not read (_tick); ticks that enqueued
        # theirs while it was unread; token steps a program ran for a
        # slot as live after the slot's EOS (read one tick late)
        self._inflight: "_Tick | None" = None
        self._lookahead_ticks = 0
        self._lookahead_discarded = 0
        # the tick ledger: engine thread only; closed (and read by
        # stats()) under _stats_lock.  No switch.
        self._ledger = StepPhaseLedger(
            component="engine", phases=TICK_PHASES,
            histogram=_TICK_PHASE_SECONDS, coverage_gauge=None,
            idle_phase="idle_wait", overhead_phase="finish")
        # the request-stage ledger (module docstring): written and read
        # under _stats_lock
        self._stages = RequestStageLedger(
            _STAGE_LANES, tails={"queue_wait": _QUEUE_WAIT_SECONDS,
                                 "ttft": _TTFT_SECONDS})
        self._wait_cause_s = dict.fromkeys(WAIT_CAUSES, 0.0)
        self._decode_tokens = 0
        self._decode_s = 0.0
        # the device's queue as the host knows it: programs enqueued,
        # and of them those a read has proven run (engine thread only);
        # summed at every enqueue: how many were ahead of the new one
        self._enqueued = 0
        self._ran = 0
        self._device_queue_sum = 0
        self._device_enqueues = 0
        self._chunk_lane_busy_s = 0.0
        self._t0 = time.monotonic()
        # on a mesh, pin the pool cache's sharding on every step/insert
        # output so the layout is stable from step 1 (inference-only
        # propagation would re-specialise the jit once per layout change
        # and thrash the donation)
        sh, rep = self._cache_shardings(slots), self._rep
        self._program("step", jax.jit(
            self._step_impl, donate_argnums=(0,),
            out_shardings=(sh, rep, rep, rep)))
        self._program("insert", jax.jit(
            self._insert_impl, donate_argnums=(0,), out_shardings=(sh, rep)))
        if self._block:
            self._program("pass", jax.jit(self._pass_impl,
                                          donate_argnums=(0,)))
        # -- speculative decoding (draft-k / verify-once rounds) --
        # a tick whose progress only the device knows is read before the
        # next is enqueued: a speculative round, a pass under dynamic
        # remasking (_tick)
        self._reads_own = k > 0 or (self._block > 0
                                    and self._remasking == "dynamic")
        self._spec_k = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._draft_cache = None
        if k > 0:
            if draft_cfg is None or draft_params is None:
                raise ValueError(
                    "spec_k > 0 requires draft_cfg + draft_params (a "
                    "smaller model over the same vocabulary)")
            if temperature > 0:
                raise ValueError(
                    "speculative decoding is greedy-only (temperature "
                    "<= 0): acceptance compares the draft against the "
                    "target's argmax, which is what keeps the output "
                    "bit-identical to plain decode")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target "
                    f"vocab {cfg.vocab_size}")
            self._spec_k = k
            # rounds per tick sized so a tick still consumes about
            # steps_per_sync tokens at full acceptance
            self._spec_rounds = max(1, self._T // (k + 1))
            self._draft_dcfg = dataclasses.replace(
                draft_cfg, decode=True, attention_impl="dense", mesh=mesh,
                max_len=cache_len)
            self._draft_model = TransformerLM(self._draft_dcfg)
            dsplit = _split_layer_params(draft_params, draft_cfg.num_layers)
            if mesh is not None:
                # the draft is small by contract: replicate it (and its
                # cache) rather than threading a second sharding family
                dsplit = jax.device_put(dsplit, rep)
            self._draft_params = dsplit
            self._draft_cache = self._draft_fresh_cache(slots)
            # the verify model shares the target's params and cache
            # layout but scatters multi-token writes at PER-EXAMPLE
            # indices — each slot verifies its k+1 candidates from its
            # own position (transformer.TransformerConfig.decode_scatter)
            self._vmodel = TransformerLM(dataclasses.replace(
                self._dcfg, decode_scatter=True))
            self._program("spec", jax.jit(
                self._spec_impl, donate_argnums=(0, 1),
                out_shardings=(sh, rep, rep, rep, rep)))
            self._program("draft_insert", jax.jit(
                self._place, donate_argnums=(0,), out_shardings=rep))
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="continuous-batcher")
        self._thread.start()

    def _program(self, family: str, jitted) -> None:
        """``self._<family>_jit``: a program built here, compiled by
        :meth:`warm` and first called from a tick.  Both are spans
        ``build/engine/<family>`` (a program lowered twice shows twice
        under one name); after its first call the attribute is the bare
        jitted function."""
        name = f"_{family}_jit"
        setattr(self, name, PROGRAM_BUILDS.first_call(
            jitted, "engine", family, None, self, name))

    # -- public --------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               session: str | None = None) -> Future:
        """Queue one prompt (1-D int32).  The future resolves to the
        generated tokens (≤ max_new_tokens; truncated at eos_id).
        ``session`` (paged-KV engines) pins the finished conversation's
        KV chain so the session's next turn — routed back here by the
        gateway's affinity — resumes from it instead of re-prefilling,
        and marks the chain for migration on drain()."""
        ids = np.asarray(prompt, np.int32).reshape(-1)
        cache_len = self._dcfg.max_len
        if len(ids) == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(ids) >= cache_len:
            raise ValueError(
                f"prompt length {len(ids)} must leave room for at least "
                f"one generated token (cache_len {cache_len})")
        if len(ids) + max_new_tokens > cache_len:
            raise ValueError(
                f"prompt {len(ids)} + new {max_new_tokens} exceeds "
                f"max_len {cache_len}")
        req = _Request(ids, max_new_tokens, session)
        if self._block:
            # whole blocks of the prompt are prefilled; what is left
            # over opens the first block as given tokens
            P0 = len(ids) // self._block * self._block
            req.ids, req.given = ids[:P0], ids[P0:]
        with self._enqueue_lock:
            if self._stopping:
                raise RuntimeError("engine stopping")
            if self._draining:
                raise RuntimeError("engine draining")
            self._submitted_requests += 1
            self._queue.put(req)
        return req.future

    def generate(self, prompt: np.ndarray, max_new_tokens: int,
                 timeout: float | None = None) -> np.ndarray:
        """Blocking convenience wrapper around :meth:`submit`."""
        return self.submit(prompt, max_new_tokens).result(timeout)

    def run_on_engine(self, fn, timeout: float = 30.0):
        """Run ``fn()`` on the engine thread between ticks and return
        its result.  The single-writer rule for device state extends to
        the KV block pool — imports and any future cache surgery go
        through here rather than racing the tick loop."""
        task = _Task(fn)
        with self._enqueue_lock:
            if self._stopping:
                raise RuntimeError("engine stopping")
            self._queue.put(task)
        return task.future.result(timeout)

    def import_session(self, session: str, tokens: list[int], meta: dict,
                       blob: bytes) -> int:
        """Adopt one migrated session chain (engine-thread-executed);
        returns the number of blocks newly uploaded.  Raises on a
        paging-disabled engine or a layout mismatch — the exporter falls
        back to letting the session cold-start elsewhere."""
        if self._kv is None:
            raise RuntimeError("paged KV cache disabled on this engine")
        return self.run_on_engine(
            lambda: self._kv.import_chain(session, tokens, meta, blob))

    def kv_pinned_sessions(self) -> list[str] | None:
        """Best-effort any-thread snapshot of pinned session ids ([] on
        unpaged engines).  Returns None when a concurrent engine-thread
        pin/unpin raced the iteration — callers polling (the replica's
        pin pruner) just retry next period."""
        if self._kv is None:
            return []
        try:
            return self._kv.sessions()
        except RuntimeError:
            return None

    def export_sessions(self) -> list[tuple[str, list[int], dict, bytes]]:
        """``[(session, tokens, meta, blob)]`` for every pinned session
        chain.  Only legal once the engine thread has stopped (after
        :meth:`drain`/:meth:`stop`) — the drain()-then-migrate path."""
        if self._kv is None:
            return []
        if self._thread.is_alive():
            raise RuntimeError(
                "export_sessions() requires a stopped engine (call "
                "drain() first)")
        out = []
        for session in self._kv.sessions():
            chain = self._kv.reusable(self._kv.chain_of(session))
            if not chain:
                continue
            meta, blob = self._kv.export_chain(chain)
            out.append((session, self._kv.chain_tokens(chain), meta, blob))
        return out

    def warm(self, prompt_len: int, chain_blocks=None,
             chunk_finals: bool = False) -> None:
        """Compile everything serving ``prompt_len``-class prompts can
        hit — the decode step and the prefill + insert pair at every
        PREFILL_KS sub-batch size — BEFORE traffic arrives.
        ``chain_blocks`` (an iterable of chain depths in blocks) limits
        the reuse-prefill family to the padded depths those chains
        reach, where the caller knows its traffic's (a document cell's
        chains are hundreds of blocks deep and never eight); None warms
        every admissible depth.  ``chunk_finals`` also compiles the
        chunk lane's last-chunk program for EVERY bucket up to the chunk
        size (a long prompt's remainder can land on any of them), not
        only the one ``prompt_len`` lands on.  A compile
        inside the serving path stalls every live lane (minutes on a
        remote-compiler backend); call this after construction, before
        submitting.  Thread-safe only while no requests are in flight —
        ENFORCED here: a warm() racing live traffic shares the donated
        pool-cache buffers with the engine thread's step/insert jits,
        so misuse must fail loudly, not corrupt running generations.
        The guard counts submitted-vs-completed requests (not slot/
        queue state, which goes momentarily empty while the engine
        thread is mid-admission between queue pop and slot insert)."""
        with self._enqueue_lock, self._stats_lock:
            in_flight = (self._submitted_requests - self._done_requests
                         - self._failed_requests)
        if in_flight:
            raise RuntimeError(
                f"ContinuousBatcher.warm() called with {in_flight} "
                "request(s) in flight; warm() must run after "
                "construction, before the first submit()")
        key = jax.random.key(0)
        P = self._bucket(prompt_len)
        for K in self.PREFILL_KS:   # __init__ already filtered by slots
            ids = jnp.zeros((K, P), jnp.int32)
            lens = jnp.ones((K,), jnp.int32)
            at = (jnp.zeros((K,), jnp.int32) if self._recurrent
                  else None)
            slab, toks, _, snap = self._prefill_fn(P, K)(
                self._params, ids, lens, key, at)
            if snap is not None and self._kv is not None:
                # the snapshot an admission takes of its state layers
                self._kv.store_state(snap, 0, 0)
            # lower+compile only: executing would donate the live cache
            first = self._block_state(K) if self._block else toks
            with PROGRAM_BUILDS.build("engine", "insert", key=K):
                self._insert_jit.lower(self._cache, self._toks, slab,
                                       jnp.zeros((K,), jnp.int32),
                                       lens, first).compile()
            jax.block_until_ready(toks)
        with PROGRAM_BUILDS.build("engine",
                                  "pass" if self._block else "step"):
            (self._pass_jit if self._block else self._step_jit).lower(
                self._cache, self._toks, key, self._params,
                self._live_mask([])).compile()
        if self._chunk_tokens:
            # the program every chunked admission starts with, whatever
            # prompt class this call warms
            jax.block_until_ready(self._chunk_start())
            # chunk ladder: the mid-chunk body plus the final suffix
            # bucket this prompt class lands on (same fit guard as
            # _maybe_start_chunk — an unfittable split falls back to
            # the monolithic prefill warmed above), with
            # ``chunk_finals`` every bucket a remainder can land on
            C = self._chunk_tokens
            off = C * ((prompt_len - 1) // C)
            finals = ({b for b in self._buckets if b <= self._bucket(C)}
                      if chunk_finals else set())
            if (prompt_len > C and off + self._bucket(prompt_len - off)
                    <= self._dcfg.max_len):
                finals.add(self._bucket(prompt_len - off))
            for Pf in sorted(finals):
                if ("chunkfin", Pf) in self._prefill_cache:
                    continue            # an earlier call's
                # as an admission runs it: a start, a chunk, the last one
                slab, sown = self._chunk_start()
                slab, sown = self._chunk_mid_fn(C)(
                    self._params, slab, jnp.zeros((1, C), jnp.int32), sown)
                slab, toks, *_ = self._chunk_final_fn(Pf)(
                    self._params, slab, jnp.zeros((1, Pf), jnp.int32),
                    jnp.ones((1,), jnp.int32), sown, key,
                    jnp.zeros((1,), jnp.int32) if self._recurrent
                    else None)
                jax.block_until_ready(toks)
        if self._spec_k:
            for K in self.PREFILL_KS:
                dslab = self._draft_prefill_fn(P, K)(
                    self._draft_params, jnp.zeros((K, P), jnp.int32),
                    jnp.ones((K,), jnp.int32))
                with PROGRAM_BUILDS.build("engine", "draft_insert", key=K):
                    self._draft_insert_jit.lower(
                        self._draft_cache, dslab,
                        jnp.zeros((K,), jnp.int32),
                        jnp.ones((K,), jnp.int32)).compile()
                jax.block_until_ready(jax.tree.leaves(dslab)[0])
            # lower+compile only: executing would donate the live caches
            with PROGRAM_BUILDS.build("engine", "spec"):
                self._spec_jit.lower(self._cache, self._draft_cache,
                                     self._toks, self._params,
                                     self._draft_params).compile()
        if self._kv is not None and self._ringed:
            # the snapshot an admission takes at its prompt's end
            self._kv.store_blocks(self._cache, 0, 0, [], warm=True)
        if self._kv is not None and self._reuse:
            # the reuse-prefill family too — the first prefix hit per
            # (suffix bucket, padded chain depth) must not compile on
            # the engine thread mid-traffic.  Reachable n_pads are the
            # power-of-two paddings (capped at the pool's blocks-per-
            # cache) of every chain depth the shortening guard admits.
            bs = self._kv.block
            cache_len = self._dcfg.max_len
            max_blocks = cache_len // bs
            n_pads = sorted({
                self._pad_blocks(n)
                for n in (range(1, max_blocks + 1) if chain_blocks is None
                          else chain_blocks)
                if 1 <= n and n * bs + self._buckets[0] <= cache_len})
            for n_pad in n_pads:
                # shallowest real depth that pads to n_pad — combos no
                # admissible chain can produce must not be compiled
                n_min = n_pad // 2 + 1 if n_pad > 1 else 1
                hit = (self._kv.pool, jnp.zeros((n_pad,), jnp.int32),
                       jnp.asarray(bs, jnp.int32))
                for Pb in (b for b in self._buckets if b <= P):
                    if n_min * bs + Pb > cache_len:
                        continue
                    ids = jnp.zeros((1, Pb), jnp.int32)
                    one = jnp.ones((1,), jnp.int32)
                    if self._recurrent:         # _dispatch_reuse's pair
                        _, toks, *_ = self._chunk_final_fn(Pb)(
                            self._params, self._load_prefix_fn(n_pad)(
                                *hit, self._kv.snap_arg(0)), ids, one,
                            self._zeros(("acc",), self._acc_shape, None),
                            key, jnp.zeros((1,), jnp.int32))
                    else:
                        _, toks, *_ = self._reuse_prefill_fn(Pb, n_pad)(
                            self._params, *hit, ids, one, key,
                            self._kv.snap_arg(0))
                    jax.block_until_ready(toks)

    def stats(self) -> dict:
        with self._stats_lock:
            dt = max(1e-9, time.monotonic() - self._t0)
            active = sum(not s.free for s in self._slots)
            lanes = max(1, self._lane_steps)
            return {
                "slots": len(self._slots),
                "active_slots": active,
                "queue_depth": self._queue.qsize() + len(self._pending),
                "requests_done": self._done_requests,
                "tokens_emitted": self._emitted_tokens,
                "tokens_per_s": round(self._emitted_tokens / dt, 1),
                # fraction of dispatched lane-steps that served a live
                # request (the rest is free-slot ballast)
                "slot_utilization": round(self._active_lane_steps / lanes, 3),
                # what the model's layers count, by cache class and by
                # sown name (model_counters.KEYS says what each is)
                **self._counters.totals(),
                # host-side time spent dispatching prefill work while
                # decode lanes were live — the upper bound on decode
                # wall-time lost to admissions (device work still
                # serialises on one chip; this is the schedule cost)
                "prefill_stall_s": round(self._prefill_stall_s, 3),
                "max_prompt_len": self._dcfg.max_len - 1,
                "uptime_s": round(dt, 3),
                "draining": self._draining,
                # chunked prefill: dispatch/admission counters (0s when
                # off or no prompt ever exceeded the chunk size)
                "prefill_chunk": self._chunk_tokens,
                "prefill_chunks": self._prefill_chunks,
                # ticks in which the lane advanced two prompts
                "chunk_pair_dispatches": self._chunk_pair_dispatches,
                "chunked_admissions": self._chunked_admissions,
                **self._build_stats(),
                **self._tick_stats(),
                **self._kv_stats(),
                **self._spec_stats(),
                **self._block_stats(),
            }

    def _build_stats(self) -> dict:
        """What the ENGINE THREAD built since start, from the
        program-build ledger: programs and seconds, spans and
        unlabelled compiles alike.  ``warm()`` builds on its caller's
        thread, so differenced over a window of traffic these are the
        compiles that stalled the tick loop inside it."""
        n, s = PROGRAM_BUILDS.thread_totals(self._thread.ident or 0)
        return {"program_builds": n, "program_build_s": s}

    def _tick_stats(self) -> dict:
        """The tick ledger and the request-stage ledger, cumulative (a
        reader differences two calls).  ``tick_coverage`` alone is a
        LEVEL, the tick ledger's EMA: whoever differences every numeric
        key (``benchmarks/runners/serve.py``) gets a meaningless number
        under that name, and no reader takes it from there.  ``tick_s``
        is the wall time of the ticks, ``idle_wait_s`` the time between
        them: together the engine thread's life.  The ``_sum``/count
        pairs give means, the ``stage_<stage>_le_<edge>`` bucket counts
        the tails (module docstring)."""
        tot = self._ledger.totals()
        ph = tot["phases"]
        stages = self._stages.totals()
        return {
            "ticks": tot["steps"],
            "tick_s": tot["wall_s"],
            **{f"tick_{p}_s": ph[p] for p in TICK_PHASES
               if p != "idle_wait"},
            "idle_wait_s": ph["idle_wait"],
            "tick_coverage": tot["coverage"] or 0.0,
            "lookahead_ticks": self._lookahead_ticks,
            "lookahead_discarded_token_steps": self._lookahead_discarded,
            # the names three accepted readers know
            "admitted": stages["stage_queue_wait_n"],
            "queue_wait_s_sum": stages["stage_queue_wait_sum_s"],
            "first_tokens": stages["stage_ttft_n"],
            "ttft_s_sum": stages["stage_ttft_sum_s"],
            "decode_tokens": self._decode_tokens,
            "decode_s_sum": self._decode_s,
            **{f"queue_wait_cause_{c}_s": v
               for c, v in self._wait_cause_s.items()},
            "device_enqueues": self._device_enqueues,
            "device_queue_programs_sum": self._device_queue_sum,
            "chunk_lane_busy_s": self._chunk_lane_busy_s,
            **stages,
        }

    def observe_stage(self, stage: str, seconds: float) -> None:
        """A stage of a request's life that ends OUTSIDE the engine
        (``deliver``: the replica stamps the answer's way out), into
        the same ledger.  Any thread."""
        with self._stats_lock:
            self._stages.observe(stage, seconds)

    def _stage(self, req: "_Request", stage: str) -> None:
        """``stage`` of ``req`` is closed (both its stamps are set):
        into the ledger, under its lane where the stage has lanes.
        Under ``_stats_lock``."""
        self._stages.observe(stage, req.stage_s(stage),
                             req.lane if _STAGE_LANES[stage] else None)

    def _count_enqueue(self) -> None:
        """One more program in the device's queue, behind ``ahead``
        others the host has not yet seen run."""
        ahead = self._enqueued - self._ran
        self._enqueued += 1
        with self._stats_lock:
            self._device_enqueues += 1
            self._device_queue_sum += ahead

    def _spec_stats(self) -> dict:
        """Speculative-decode counters (empty when spec is off, so
        stats() consumers see the plain shape unchanged)."""
        if not self._spec_k:
            return {}
        prop = max(1, self._spec_proposed)
        return {
            "spec_k": self._spec_k,
            "spec_proposed": self._spec_proposed,
            "spec_accepted": self._spec_accepted,
            "spec_accept_rate": round(self._spec_accepted / prop, 3),
        }

    def _kv_stats(self) -> dict:
        """Paged-KV counters (empty when paging is off, so stats()
        consumers see the pre-paged shape unchanged)."""
        if self._kv is None:
            return {}
        return {
            "kv_block": self._kv.block,
            "kv_blocks_used": self._kv.blocks_used(),
            "kv_blocks_free": self._kv.blocks_free(),
            "kv_prefix_hits": self._kv_hits,
            "kv_prefix_misses": self._kv_misses,
            "kv_prefill_tokens": self._prefill_tokens,
            "kv_prefill_tokens_skipped": self._prefill_tokens_skipped,
            "kv_evictions": self._kv.evictions,
            "kv_commit_skips": self._kv.commit_skips,
            "kv_sessions": self._kv.session_count(),
            "kv_window_snapshots": self._kv.snaps_used(),
            "kv_window_snapshot_skips": self._kv.snap_skips,
            # state-space layers (0s without one): snapshots held (one
            # entry serves a node's window and state layers alike),
            # prompt snapshots not taken (no free entry, or the block
            # edge lay before the prefill's last program), and pooled
            # tokens a hit prefilled again because no snapshot lay as
            # deep as the blocks (an answer's end is never snapshotted:
            # a session's next turn starts from its last PROMPT's edge)
            "kv_state_snapshots": (self._kv.snaps_used()
                                   if self._recurrent else 0),
            "kv_state_snapshot_skips": self._state_snap_skips,
            "kv_state_reprefill_tokens": self._state_reprefill,
        }

    def drain(self, timeout: float | None = None) -> bool:
        """Graceful shutdown: stop admission (submit() raises), let every
        queued + in-flight request run to completion, then stop the
        engine.  This is the replica-removal path — :meth:`stop` remains
        the hard path that FAILS outstanding futures.  Returns True when
        everything completed; on ``timeout`` (seconds) the engine falls
        back to the hard stop and returns False (leftover futures get
        the stop() RuntimeError, so callers never hang either way).
        Idempotent and safe to call concurrently with submits: the
        draining flag and the enqueue share one lock, so a submit either
        lands before the flag (and completes) or raises."""
        with self._enqueue_lock:
            self._draining = True
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._enqueue_lock, self._stats_lock:
                in_flight = (self._submitted_requests - self._done_requests
                             - self._failed_requests)
            if in_flight == 0:
                self.stop()
                return True
            if deadline is not None and time.monotonic() >= deadline:
                logger.warning("drain timed out with %d request(s) left; "
                               "falling back to hard stop", in_flight)
                self.stop()
                return False
            time.sleep(0.01)

    def stop(self) -> None:
        with self._enqueue_lock:
            self._stopping = True
        self._queue.put(None)
        self._thread.join(timeout=30.0)
        for s in self._slots:
            if s.request is not None:
                s.request.future.set_exception(
                    RuntimeError("engine stopped mid-generation"))
                s.request = None
        for st in self._chunking:          # mid-chunk admissions in flight
            st.req.future.set_exception(
                RuntimeError("engine stopped mid-prefill"))
        self._chunking = []
        while self._pending:      # engine thread joined: safe to touch
            self._pending.popleft().future.set_exception(
                RuntimeError("engine stopped"))
        while self._tasks:
            self._tasks.popleft().future.set_exception(
                RuntimeError("engine stopped"))
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is not None:   # requests and tasks both carry a future
                req.future.set_exception(RuntimeError("engine stopped"))

    # -- device state construction -------------------------------------------
    def _require_fit(self, slots: int, kv_block: int,
                     pool_blocks: int, n_snaps: int = 0) -> int:
        """Refuse at construction, with the sizes, an engine whose slot
        slabs + block pool + largest prefill dispatch cannot fit what
        the device has left — instead of an XLA allocation error on
        whichever request first needs the memory.  Backends that report
        no limit (CPU) are not checked.

        Returns the number of layer-state snapshots the pool gets:
        ``n_snaps`` as asked, but for a state-space configuration, whose
        snapshot is a whole slot state: there the count starts at
        ``slots // 2`` (+ the scratch entry), the prefill ladder is
        fitted beside that, and what is left over goes to snapshots, up
        to one a slot."""
        dev = (self._mesh.devices.flat[0] if self._mesh is not None
               else jax.devices()[0])
        stats = dev.memory_stats() or {}
        limit = stats.get("bytes_limit")
        if not limit:
            return n_snaps
        tp = dict(self._mesh.shape).get("tp", 1) if self._mesh else 1
        one_lane = self._cache_shapes(1)
        wanted = n_snaps
        if self._recurrent and n_snaps:
            wanted = min(wanted, slots + 1)
            n_snaps = min(n_snaps, slots // 2 + 1)
        # every cache leaf is [lanes, ...]: one lane's bytes, split over
        # tp where the layer's class shards (_cache_shardings' rule)
        lane = sum(s.size * s.dtype.itemsize
                   // (tp if s.ndim >= 2
                       and self._classes[name].sharded(node, tp) else 1)
                   for name, node in one_lane.items()
                   for s in jax.tree.leaves(node))
        cache_len = self._dcfg.max_len
        def pool_bytes(n):
            return (pool_device_bytes(one_lane, kv_block, pool_blocks, tp,
                                      self._classes, n)
                    if kv_block > 0 else 0)

        pool = pool_bytes(n_snaps)
        # the widest admission: K fresh lanes, their [K, P, vocab] f32
        # logits and one layer's [K, heads, P, cache_len] f32 scores (a
        # multi-token call attends the whole slab under its mask; a
        # latent layer's a tile of it at a time: ``scores``), P the
        # largest monolithic bucket (prompts past the chunk size prefill
        # one lane, one chunk).  The sub-batch ladder is cut to the
        # widest K that fits: 8 lanes of 64 heads against a 16k slab
        # are 8.6 GB of scores, one lane is 1.1
        p_max = self._bucket(min(self._chunk_tokens or cache_len,
                                 cache_len - 1))
        heads = self.cfg.num_heads // (tp if self.cfg.num_heads % tp == 0
                                       else 1)
        in_use = stats.get("bytes_in_use", 0)
        # what the layers' classes say a lane's multi-token call costs
        # beside its cache: the widest scan's temporaries, and the
        # widest attention's in a k-lane call
        scan = max(c.scan_bytes(p_max) for c in self._kinds)

        def scores(k):
            return max(c.scores_bytes(k, p_max, heads, cache_len)
                       for c in self._kinds)

        # the head's float32 logits: every row of a lane, or under the
        # last-position cut the one it samples at
        rows = 0 if self._block else 1 if self._cut else p_max
        for i, k_max in enumerate(self.PREFILL_KS):
            prefill = k_max * (lane + scan + 4 * rows * self.cfg.vocab_size
                               + scores(k_max))
            need = in_use + slots * lane + pool + prefill
            if need <= limit:
                if i:
                    logger.info(
                        "prefill sub-batches cut from %d to %d lanes: %d "
                        "lanes x %d tokens against a %d-token slab would "
                        "not fit beside the weights", self.PREFILL_KS[0],
                        k_max, self.PREFILL_KS[0], p_max, cache_len)
                    self.PREFILL_KS = self.PREFILL_KS[i:]
                if n_snaps < wanted:
                    each = pool_bytes(n_snaps + 1) - pool
                    n_snaps = min(wanted,
                                  n_snaps + int((limit - need) // each))
                return n_snaps
        gb = 1 / (1 << 30)
        raise ValueError(
            f"engine does not fit {dev.device_kind}: "
            f"{in_use * gb:.2f} GiB already resident + "
            f"{slots * lane * gb:.2f} GiB slot slabs ({slots} slots x "
            f"{cache_len} tokens; "
            f"{sum(c.window > 0 for c in self._classes.values())} window "
            f"layers hold a ring of {self._dcfg.ring_len}) + "
            f"{pool * gb:.2f} GiB block pool "
            f"({pool_blocks} blocks of {kv_block}, {n_snaps} " + (
                "layer-state snapshots" if self._recurrent
                else "window snapshots") + ") + "
            f"{prefill * gb:.2f} GiB widest prefill ({k_max} lanes x "
            f"{p_max} tokens) = {need * gb:.2f} GiB > "
            f"{limit * gb:.2f} GiB limit; lower --slots/--max_len or "
            f"set --kv_pool_blocks")

    def _cache_shapes(self, B: int):
        """Shape tree of a ``B``-lane decode cache (``_lanes``)."""
        return self._lanes("target", self._model, B)

    def _lanes(self, which: str, model, B: int):
        """One lane's cache shapes with every leaf's first axis (its
        lanes: what ``_place`` scatters on) ``B`` long.  ``model.init``
        is traced ONCE, for one lane, and never again (re-tracing it
        cost a chunked admission 250 ms of host Python with the device
        idle: PERF.md, PR 25)."""
        one = self._shape_memo.get(which)
        if one is None:
            ids = jnp.zeros((1, 1), jnp.int32)
            one = self._shape_memo[which] = jax.eval_shape(
                lambda: model.init(jax.random.key(0), ids,
                                   positions=ids))["cache"]
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((B, *s.shape[1:]), s.dtype), one)

    def _sown_layout(self, slots: int) -> tuple:
        """What this configuration's layers sow
        (``generate.sown_layout``), read off ``eval_shape``s of the
        decode model's two calls: the step's one-token call and the
        narrowest multi-token call (what a kernel sows only where a
        call is small enough for it, ``ops/moe.prefix_rows``, the
        narrowest call sows).  ``init`` cannot say: a layer's first
        call has no cache yet and takes another branch.  A name these
        two missed is refused where its program is traced
        (``sown_vector``), in ``warm()``."""
        def sown(lanes, width):
            def call(params):
                ids = jnp.zeros((lanes, width), jnp.int32)
                _, mut = self._model.apply(
                    {"params": params,
                     "cache": _zeros_of(self._cache_shapes(lanes))},
                    ids, positions=ids, token_mask=ids == 0,
                    mutable=["cache", "intermediates"],
                    **self._last(jnp.zeros((lanes,), jnp.int32)))
                return mut.get("intermediates", {})
            return jax.eval_shape(call, self._params)

        if self._block:       # the step is a pass, of the pass model
            def a_pass(params):
                ids = jnp.zeros((slots, 2 * self._block), jnp.int32)
                _, mut = self._pmodel.apply(
                    {"params": params,
                     "cache": _zeros_of(self._cache_shapes(slots))},
                    ids, positions=ids, token_mask=ids == 0,
                    mutable=["cache", "intermediates"])
                return mut.get("intermediates", {})
            return sown_layout(jax.eval_shape(a_pass, self._params),
                               sown(1, self._buckets[0]))
        return sown_layout(sown(slots, 1), sown(1, self._buckets[0]))

    def _zeros(self, key: tuple, shapes, shardings):
        """A fresh zeroed cache from ONE compiled program per ``key``,
        born with its sharding.  The program is kept, never the array:
        the chunk programs donate their slab."""
        fn = self._prefill_cache.get(key)
        if fn is None:
            def zeros():
                return _zeros_of(shapes)

            fn = self._prefill_cache[key] = PROGRAM_BUILDS.first_call(
                jax.jit(zeros, out_shardings=shardings), "engine", key[0],
                key, self._prefill_cache, key)
        return fn()

    def _fresh_cache(self, B: int):
        return self._zeros(("zeros", B), self._cache_shapes(B),
                           self._cache_shardings(B))

    def _cache_shardings(self, B: int):
        """A ``B``-lane cache's shardings; None off a mesh (``jax.jit``'s
        "unspecified").  A layer's buffers shard over ``tp`` on the
        kv-head axis (axis 1 of [B, Hk, ...]) where its class says they
        do (``CacheClass.sharded``: when the heads divide); cache_index
        and the others (e.g. MQA with Hk < tp) replicate — GSPMD still
        shards the q-head compute from the param shardings either way."""
        if self._mesh is None:
            return None
        from jax.sharding import NamedSharding
        return jax.tree.map(
            lambda spec: NamedSharding(self._mesh, spec),
            cache_layout.cache_specs(self._classes, self._cache_shapes(B),
                                     dict(self._mesh.shape).get("tp", 1)))

    # -- jitted pieces -------------------------------------------------------
    def _sample(self, logits, key):
        """[B, V] -> [B]; THE generate() sampling recipe (shared
        helper — the two serving paths must never diverge)."""
        return sample_logits(logits, key, temperature=self._temperature,
                             top_k=self._top_k, top_p=self._top_p)

    @_compiled("prefill", lambda P, K: (P, K))
    def _prefill_fn(self, P: int, K: int):
        """Compiled per (prompt bucket, sub-batch size): fresh K-lane
        cache, prompt kv, one sampled next token per lane."""
        model = self._model

        def prefill(params, ids, true_lens, key, snap_at=None):
            cache = _zeros_of(self._cache_shapes(K))
            # pad positions are masked out of MoE routing (they must
            # not claim expert capacity ahead of real tokens' choices;
            # with ample capacity the padded prefill matches generate()
            # exactly — under a tight capacity_factor the bucket's
            # larger static capacity can only drop FEWER real tokens,
            # see MoEMLP's docstring)
            logits, mut = model.apply(
                {"params": params, "cache": cache}, ids,
                positions=jnp.broadcast_to(jnp.arange(ids.shape[1]),
                                           ids.shape),
                token_mask=jnp.arange(ids.shape[1])[None, :]
                < true_lens[:, None],
                snap_at=snap_at, mutable=_PREFILL_MUTABLE,
                **self._last(true_lens - 1))
            # padded prompts: sample each lane at ITS last real
            # position; the pad queries wrote kv past true_len, which
            # insertion resets (cache_index := true_len) and masks
            # never reach
            toks = self._first(logits, true_lens - 1, key)
            return (mut["cache"], toks, self._sown(mut), self._snap_of(mut))

        return jax.jit(prefill)

    def _last(self, at) -> dict:
        """What a multi-token program that samples at row ``at`` [lanes]
        of its call hands the model beside its tokens: under the
        last-position cut that row, else nothing."""
        if self._block:       # a block engine's prefill samples nothing
            return {"return_hidden": True}
        return {"last_at": at} if self._cut else {}

    def _first(self, logits, at, key):
        """``[lanes]``: the first token a multi-token program samples
        for each lane, at row ``at`` of the call.  A block engine's
        first tokens come out of the first block's passes: zeros, which
        nothing reads."""
        if self._block:
            return jnp.zeros((logits.shape[0],), jnp.int32)
        return self._sample(self._last_row(logits, at), key)

    def _last_row(self, logits, at):
        """``[lanes, vocab]``: each lane's logits at row ``at`` of the
        call (under the last-position cut the one row the model
        returned)."""
        if self._cut:
            return logits[:, 0]
        return jnp.take_along_axis(logits, at[:, None, None], axis=1)[:, 0]

    @staticmethod
    def _snap_of(mut):
        """``{layer: {leaf: [lanes, ...]}}`` out of a prefill's ``snap``
        collection (what its recurrent layers sowed at ``snap_at``), the
        leaves named as the pool names them
        (``cache_layout.state_leaves``); None where no layer sows one:
        those programs are what they were."""
        if "snap" not in mut:
            return None
        return {name: cache_layout.state_leaves(node)
                for name, node in mut["snap"].items()}

    def _sown(self, mut) -> "jax.Array":
        """What a program's layers sowed, as the one vector every
        program returns (``generate.sown_vector`` under this
        configuration's layout)."""
        return sown_vector(mut.get("intermediates"), self._counters.layout)

    def _snap_end(self, req: "_Request") -> int:
        """Where a prompt is snapshotted for the pool: the deepest block
        edge the SAME prompt can match again (a hit leaves at least one
        token to prefill); 0 without layer-state snapshots."""
        if self._kv is None or not self._snapped:
            return 0
        return (len(req.ids) - 1) // self._kv.block * self._kv.block

    @staticmethod
    def _place(cache, slab, slots, true_lens):
        """Scatter a K-lane prefill cache into slots ``slots`` of the
        pool cache and reset those slots' indices to ``true_lens``."""
        def put(big, small):
            if small.ndim == 1:                       # cache_index [K]
                return big.at[slots].set(true_lens)
            # kv buffers: [K, ...] lanes -> the pool's [n_slots, ...]
            return big.at[slots].set(small)
        return jax.tree.map(put, cache, slab)

    @staticmethod
    def _insert_impl(cache, toks, slab, slots, true_lens, first):
        """:meth:`_place` the admission's slab, and its ``first``
        sampled tokens ([K]) into the slots' entries of ``toks``, the
        vector the next step feeds: the tokens never visit the host on
        their way there.  (A block engine's ``toks`` is the slots'
        block state, a tree; ``first`` the admissions' rows of it.)"""
        return (ContinuousBatcher._place(cache, slab, slots, true_lens),
                jax.tree.map(lambda t, f: t.at[slots].set(f), toks, first))

    def _step_impl(self, cache, toks, key, params, live):
        """Advance every slot ``self._T`` tokens (one dispatch).

        ``live`` ([slots] bool) marks the slots that hold a request.
        It reaches every layer as the step's ``token_mask``: on the
        chip the decode kernels neither write nor read a free slot's
        slab (transformer.Block._decode_attention), and the dropless
        expert path routes free slots' ballast tokens nowhere.  What
        the layers sow rides back beside the tokens, summed over the
        token steps: ``(cache, last, tokens [slots, T], counters)``,
        ``counters`` the one vector of ``_sown`` (zero-width where the
        configuration's layers sow nothing).
        ``last`` ([slots]) is what the final token step sampled: the
        next call's ``toks``, handed over on the device.

        ``params`` is an ARGUMENT, not a closure capture: a captured
        param tree would be baked into the jaxpr as constants — 124M
        f32 literals at the flagship config, which every compile and
        every compile-cache key would then have to carry."""
        model = self._model

        def one(carry, k):
            cache, tok, acc = carry
            # per-slot positions come from the cache itself
            pos = self._positions(cache)
            logits, mut = model.apply(
                {"params": params, "cache": cache}, tok[:, None],
                positions=pos[:, None], token_mask=live[:, None],
                mutable=["cache", "intermediates"])
            nxt = self._sample(logits[:, -1], k)
            return (mut["cache"], nxt, acc + self._sown(mut)), nxt

        (cache, last, acc), out = jax.lax.scan(
            one, (cache, toks, _zeros_of(self._acc_shape)),
            jax.random.split(key, self._T))
        return cache, last, out.T, acc          # tokens [slots, T]

    @staticmethod
    def _positions(cache):
        """Current per-slot sequence positions: any layer's cache_index."""
        for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
            if leaf.ndim == 1:
                return leaf
        raise AssertionError("no cache_index leaf found")

    # -- generation by diffusion over blocks ----------------------------------
    def _init_block(self, L: int, spec_k: int, mesh, cache_len: int,
                    kv_block: int, prefill_chunk) -> None:
        """What a block engine (``cfg.block_length = L``) refuses, and
        its unmask rule (the configuration's ``block_steps``,
        ``block_remasking``, ``block_threshold``, ``block_mask_id``)."""
        if spec_k > 0:
            raise ValueError(
                f"speculative decoding (spec_k > 0) does not serve a "
                f"block_length = {L} configuration: a pass yields a whole "
                f"block or nothing, there is no next token for a draft to "
                f"guess and no rewind of a committed block")
        if mesh is not None:
            raise ValueError(
                f"a mesh engine does not serve a block_length = {L} "
                f"configuration: the pass program's append and attend are "
                f"written for slabs no mesh shards")
        chunk = (constants.PREFILL_CHUNK if prefill_chunk is None
                 else prefill_chunk)
        for name, n in (("max_len", cache_len), ("kv_block", kv_block),
                        ("prefill_chunk", chunk)):
            if n % L:
                raise ValueError(
                    f"block_length {L} must divide {name} ({n}): blocks, "
                    f"pool blocks and prompt chunks start at its multiples")
        cfg = self.cfg
        self._remasking = cfg.block_remasking
        self._threshold = float(cfg.block_threshold)
        self._mask_id = int(cfg.block_mask_id)
        # positions a denoise pass unmasks (static): a constant of the
        # pass program until ``submit`` takes a step count a request
        self._unmask = -(-L // (int(cfg.block_steps) or L))
        # passes enqueued so far: the clock the slots' blocks keep step
        # by (``_start_wait``)
        self._passes_enqueued = 0
        self._blk = dict.fromkeys(_BLOCK_KEYS, 0)
        # a dict here (set by whoever wants the unmask order: the
        # benchmark's checks, a test) receives, under each request's
        # future, one record a pass the request was live in: the block
        # as the pass found it (``tok``, ``masked``), whether the pass
        # committed it, how many slots its dispatch had live, and which
        # pass of the engine's it was (``at``)
        self.pass_log: dict[Future, list] | None = None

    def _block_state(self, lanes: int, reqs=()):
        """``lanes`` slots' open blocks, what the pass program carries
        in place of a last token: ``tok [lanes, L]``, ``masked [lanes,
        L]`` (a flag, not a comparison with the mask id: a prompt may
        hold that id), ``left [lanes]``, block positions the slot has
        yet to commit (0: a free slot), and ``wait [lanes]``, passes the
        slot sits out before its first (``_start_wait``).  With ``reqs``
        the first blocks of those admissions: the prompt's tail given,
        the rest masked."""
        L = self._block
        tok = np.full((lanes, L), self._mask_id, np.int32)
        masked = np.ones((lanes, L), bool)
        left, wait = (np.zeros((lanes,), np.int32) for _ in range(2))
        for i, req in enumerate(reqs):
            r = len(req.given)
            tok[i, :r], masked[i, :r] = req.given, False
            left[i], wait[i] = r + req.max_new, req.wait
        return {"tok": jnp.asarray(tok), "masked": jnp.asarray(masked),
                "left": jnp.asarray(left), "wait": jnp.asarray(wait)}

    def _first_blocks(self, reqs: list, lanes_live: bool):
        """The block state of admissions whose first pass is the next
        dispatch's first, each with its ``_start_wait``."""
        for r in reqs:
            r.wait = self._start_wait(len(r.given), lanes_live)
        with self._stats_lock:
            self._blk["blockdiff_given_tokens"] += sum(
                len(r.given) for r in reqs)
        return self._block_state(len(reqs), reqs)

    def _start_wait(self, given: int, lanes_live: bool) -> int:
        """Passes a slot admitted in this tick sits out so that its
        commits fall in the passes every other slot's fall in: under the
        static rule a block takes ``ceil(L / unmask)`` passes, the first
        of which carries the commit of the block before it, and only a
        pass in which some slot commits runs the forward of ``2 L`` rows
        (``_pass_impl``).  With the commits in step three passes of four
        (``L`` = steps = 4) are a forward of ``L`` rows.  The slot's
        first pass is the first of the next dispatch, this tick's passes
        (if a slot is live) before it; ``dynamic`` remasking keeps no
        step and waits for nothing."""
        if self._reads_own:
            return 0
        period = -(-self._block // self._unmask)
        first = -(-(self._block - given) // self._unmask)
        start = self._passes_enqueued + (self._T if lanes_live else 0)
        return -(start + first) % period

    def _pass_impl(self, cache, state, key, params, live):
        """``self._T`` PASSES of every live slot's open block (one
        dispatch): what :meth:`_step_impl` is to a token step.  One
        program; a pass in which some slot commits is one forward of ``2
        L`` rows a slot (:meth:`_pass_forward`): an OPEN half, the
        slot's open block (a masked position as the mask id) attended
        up to its own end, and a COMMIT half, live only in a slot that
        commits in this pass; a pass in which none does is the open half
        alone, ``L`` rows (a ``jax.lax.cond``: the commits of slots
        that keep step, ``_start_wait``, fall in the same passes).  A
        slot with a masked position unmasks ``self._unmask`` of them,
        those the model is surest of (``static``), or every one surer
        than the threshold and at least the surest (``dynamic``); its
        index stays, so the pass's K/V is overwritten by the next.  A
        slot with none COMMITS: its commit half writes the finished
        block's rows at the index, the index moves ``L`` rows, the
        block's tokens are the pass's output, and the SAME forward opens
        the next block behind it, all masked, and unmasks its first
        position(s): the commit rides the next block's first pass.
        Where the budget ends with the commit the open half is dead.  A
        slot is in a pass while the host says it is ``live``, it has
        positions ``left`` (a budget that ends inside the program stops
        its slot there) and it has sat out its ``wait``.

        Returns ``(cache, state, (out, flags) [T, slots, L] (each pass's
        block as the pass FOUND it, tokens and masked flags), counts [T,
        slots] in {0, L}, counters, passes)``: ``counters`` the layers'
        ``_sown``, ``passes`` what the program counted itself, ``[live
        (slot, pass) pairs, commits, positions unmasked, commits whose
        pass also opened the next block]``."""
        L = self._block

        def one(carry, k):
            cache, st, acc, cnt = carry
            held = live & (st["left"] > 0)
            on = held & (st["wait"] == 0)
            commit = on & ~st["masked"].any(axis=1)
            opens = on & ~(commit & (st["left"] <= L))

            def forward(cache, on):
                logits, mut = self._pass_forward(params, cache, st["tok"],
                                                 st["masked"], on)
                return logits, mut["cache"], self._sown(mut)

            # both halves only in a pass in which some slot commits: a
            # dead half still costs its rows' way through every layer
            logits, cache, sown = jax.lax.cond(
                commit.any(),
                lambda c: forward(c, opens.astype(jnp.int32) + 2 * commit),
                lambda c: forward(c, opens), cache)
            # the open block: behind a commit the next one, all masked
            tok = jnp.where(commit[:, None], self._mask_id, st["tok"])
            masked = st["masked"] | commit[:, None]
            with jax.named_scope("block_unmask"):
                x0 = self._sample(logits.reshape(-1, logits.shape[-1]),
                                  k).reshape(masked.shape)
                # softmax(logits)[x0], without the softmax's array
                conf = jnp.exp(
                    jnp.take_along_axis(logits, x0[..., None], -1)[..., 0]
                    - jax.nn.logsumexp(logits, axis=-1))
                conf = jnp.where(masked, conf, -1.0)
                order = jnp.argsort(-conf, axis=1, stable=True)
                rank = jnp.argsort(order, axis=1, stable=True)
                if self._remasking == "dynamic":
                    chosen = (conf > self._threshold) | (rank == 0)
                else:
                    chosen = rank < self._unmask
                chosen &= masked & opens[:, None]
            nxt = {
                "tok": jnp.where(chosen, x0, tok),
                "masked": masked & ~chosen,
                "left": jnp.where(commit, st["left"] - L, st["left"]),
                "wait": jnp.where(held, jnp.maximum(st["wait"] - 1, 0),
                                  st["wait"])}
            cnt = cnt + jnp.stack([
                on.sum(), commit.sum(), chosen.sum(),
                (commit & opens).sum()]).astype(jnp.float32)
            return ((cache, nxt, acc + sown, cnt),
                    (st["tok"], st["masked"], jnp.where(commit, L, 0)))

        (cache, state, acc, cnt), (out, flags, counts) = jax.lax.scan(
            one, (cache, state, _zeros_of(self._acc_shape),
                  jnp.zeros((4,), jnp.float32)),
            jax.random.split(key, self._T))
        return cache, state, (out, flags), counts, acc, cnt

    def _pass_forward(self, params, cache, tok, masked, on):
        """The forward of one pass.  With ``on [lanes]`` bool, ``L``
        rows a lane, the OPEN half alone: ``tok [lanes, L]`` (a
        ``masked`` position as the mask id) written at each lane's
        index and attended over ``[0, index + L)``, for the lanes
        ``on``.  With ``on`` int, ``2 L`` rows a lane: bit 0 the open
        half live, bit 1 the COMMIT half.  In a lane that commits
        ``tok`` is a finished block: the commit half writes it at the
        index and attends over ``[0, index + L)``, and the open half is
        the NEXT block, all masked, ``L`` rows further.  ``(the open
        half's logits [lanes, L, V], the mutated collections)``; the
        caches' indices come back moved ``L`` where a block committed
        (``Block._pass_attention``)."""
        L = self._block
        idx = self._positions(cache)
        ids = jnp.where(masked, self._mask_id, tok)
        at, live = idx[:, None], on[:, None]
        if on.dtype != jnp.bool_:
            commit, on = (on & 2) > 0, (on & 1) > 0
            ids = jnp.concatenate(
                [tok, jnp.where(commit[:, None], self._mask_id, ids)], axis=1)
            at = jnp.stack([idx, idx + jnp.where(commit, L, 0)], axis=1)
            live = jnp.stack([commit, on], axis=1)
        with jax.named_scope("block_pass"):
            return self._pmodel.apply(
                {"params": params, "cache": cache}, ids,
                positions=(at[:, :, None] + jnp.arange(L)).reshape(
                    ids.shape),
                token_mask=jnp.repeat(live, L, axis=1),
                mutable=["cache", "intermediates"])

    def _schedule_passes(self, s: "_Slot") -> None:
        """The budget of a slot the dispatch just covered, after it: the
        host runs the static unmask rule ahead of the device
        (``_pass_impl``: a block with ``m`` masked positions takes
        ``ceil(m / unmask)`` passes, the first of which, after a
        slot's first block, IS the commit of the block before it; the
        last block of a budget a pass more, its commit, which opens
        nothing), so the next tick knows who is live without a read."""
        left, m, L = s.owed, s.masked, self._block
        for _ in range(self._T):
            if left <= 0:
                break
            if s.wait:
                s.wait -= 1
                continue
            if m == 0:
                left, m = left - L, L
                if left <= 0:
                    break
            m -= min(self._unmask, m)
        s.owed, s.masked = max(left, 0), m

    def _finish_blocks(self, out: np.ndarray, counts: np.ndarray,
                       live: list, sown: np.ndarray,
                       passes: np.ndarray) -> None:
        """Consume one dispatch of passes: ``out [T, slots, L]`` with
        ``counts[t, i]`` in ``{0, L}``, the block slot ``i`` committed
        in pass ``t``.  :meth:`_finish_decode`'s contract, ragged as
        :meth:`_finish_spec`'s: of the first block the positions the
        prompt gave are not the answer's, and the last block is cut at
        the request's budget.  A lane step here is a (slot, pass); a
        pair whose commit also opened the next block (``fused``) put
        TWO blocks through the model, and ``blockdiff_slot_passes``
        counts blocks through the model: a denoise pass of a block and
        its commit one each, whichever forward carried them."""
        T, L = counts.shape[0], self._block
        out, flags = out
        mine = [(i, self._slots[i]) for i, req in live
                if self._slots[i].request is req]
        pairs, commits, unmasked, fused = (int(v) for v in passes)
        # pass t read a live slot up to its open block's end: the rows
        # prefilled, the blocks committed before it, the block (the
        # pass after the last commit of a budget finds a dead slot); a
        # pass that commits reads up to the finished block's end and,
        # where budget is left, once more up to the end of the block it
        # opens behind it
        held, logs = [], self.pass_log      # read once: its owner may
        first = self._blk["blockdiff_passes"]   # switch it off meanwhile
        for i, s in mine:
            rows = len(s.request.ids) + len(s.blocks)
            budget = s.skip + s.remaining
            log = None if logs is None else logs.setdefault(
                s.request.future, [])

            def record(t, tok, masked, commit):
                held.append(rows + L)
                if log is not None:
                    log.append({"tok": tok, "masked": masked,
                                "commit": commit, "live": len(mine),
                                "at": first + t})

            for t in range(T):
                if budget <= 0:
                    break
                if s.idle:          # a pass the slot sat out
                    s.idle -= 1
                    continue
                record(t, out[t, i].tolist(), flags[t, i].tolist(),
                       bool(counts[t, i]))
                if counts[t, i]:
                    rows, budget = rows + L, budget - L
                    if budget > 0:      # the block that forward opened
                        record(t, [self._mask_id] * L, [True] * L, False)
        with self._stats_lock:
            self._lane_steps += len(self._slots) * T
            self._active_lane_steps += pairs
            self._lookahead_discarded += (len(live) - len(mine)) * T
            b = self._blk
            b["blockdiff_passes"] += T
            b["blockdiff_slot_passes"] += pairs + fused
            b["blockdiff_blocks_committed"] += commits
            b["blockdiff_commits_fused"] += fused
            b["blockdiff_tokens_unmasked"] += unmasked
            self._counters.on_decode(held, len(live), T)
            self._counters.read(sown, (pairs + fused) * L, decode=True)
        now = time.monotonic()
        for i, s in mine:
            for t in range(T):
                if not counts[t, i]:
                    continue
                block = out[t, i].tolist()
                s.blocks.extend(block)
                new, s.skip = block[s.skip:][:s.remaining], 0
                req = s.request
                if req.t_first is None:
                    req.t_first, req.n_first = now, len(new)
                    with self._stats_lock:
                        self._stage(req, "prefill")
                        self._stage(req, "ttft")
                if self._eos is not None and self._eos in new:
                    new = new[:new.index(self._eos) + 1]
                    s.remaining = len(new)
                s.emitted.extend(new)
                s.remaining -= len(new)
                with self._stats_lock:
                    self._blk["blockdiff_tokens_delivered"] += len(new)
                if s.remaining <= 0:
                    self._finish(i)
                    break
            else:
                if self._reads_own:
                    # how far the passes got is the device's answer
                    s.owed = s.skip + s.remaining

    def _block_stats(self) -> dict:
        """Block-pass counters (empty for a causal configuration, so
        stats() consumers see the plain shape unchanged)."""
        return dict(self._blk) if self._block else {}

    # -- speculative decoding ------------------------------------------------
    def _draft_fresh_cache(self, B: int):
        return self._zeros(("draft_zeros", B),
                           self._lanes("draft", self._draft_model, B),
                           self._rep)

    @_compiled("draft", lambda P, K: ("draft", P, K))
    def _draft_prefill_fn(self, P: int, K: int):
        """Compiled per (bucket, sub-batch): the draft's prompt prefill
        beside every target admission — same padded ids/lens, no
        sampling (the draft only ever continues from the target's last
        token)."""
        draft = self._draft_model

        def dpre(params, ids, true_lens):
            cache = _zeros_of(self._lanes("draft", draft, K))
            _, mut = draft.apply(
                {"params": params, "cache": cache}, ids,
                positions=jnp.broadcast_to(jnp.arange(ids.shape[1]),
                                           ids.shape),
                token_mask=jnp.arange(ids.shape[1])[None, :]
                < true_lens[:, None],
                mutable=["cache"])
            return mut["cache"]

        return jax.jit(dpre, out_shardings=self._rep)

    def _draft_slab_for(self, req: "_Request"):
        """One-lane draft prefill from the FULL prompt — used by the
        reuse and chunked admission paths, which never fed the draft.
        The draft has no pool and no chunking on purpose: it is small
        by contract, and its state only moves the ACCEPT RATE, never
        correctness (greedy acceptance re-checks every token)."""
        P = self._bucket(len(req.ids))
        ids = np.zeros((1, P), np.int32)
        ids[0, :len(req.ids)] = req.ids
        return self._draft_prefill_fn(P, 1)(
            self._draft_params, jnp.asarray(ids),
            jnp.asarray([len(req.ids)], jnp.int32))

    def _spec_impl(self, cache, draft_cache, toks, params, draft_params):
        """``self._spec_rounds`` draft-k/verify-once rounds for every
        slot in ONE dispatch.  Per round: sync the draft to the
        target's frontier, scan k greedy draft steps, feed the last
        token + the k drafts through the VERIFY model (multi-token,
        per-example positions), and accept the longest prefix where
        draft == the target's argmax, plus the target's own next token
        (the "bonus") — so every consumed token IS the plain-greedy
        token, by induction over positions.  Rejection costs nothing to
        correctness: both caches' indices rewind to the accepted
        frontier, and the stale K/V beyond it is overwritten by the
        next round's k+1 writes before any mask can reach it (the same
        invariant padded prefill relies on).  Writes past the cache end
        are DROPPED (decode_scatter), and the host consumes at most
        ``remaining`` tokens, so overhang is dead weight, not state.

        Returns ``(cache, draft_cache, last [slots], out [R, slots,
        k+1], counts [R, slots])`` — per round, ``counts`` tokens of
        ``out`` are consumable per slot; ``last`` is the final round's
        bonus token, the next call's ``toks``."""
        k = self._spec_k
        B = len(self._slots)
        draft, vmodel = self._draft_model, self._vmodel

        def set_index(c, new_idx):
            return jax.tree.map(
                lambda leaf: new_idx if leaf.ndim == 1 else leaf, c)

        def dstep(carry, _):
            dcache, tok = carry
            logits, mut = draft.apply(
                {"params": draft_params, "cache": dcache}, tok[:, None],
                positions=self._positions(dcache)[:, None],
                mutable=["cache"])
            nxt = logits[:, -1].argmax(-1).astype(jnp.int32)
            return (mut["cache"], nxt), nxt

        def one_round(carry, _):
            cache, dcache, toks = carry
            idx = self._positions(cache)
            # the draft rides the target's frontier exactly: same last
            # token, same index (this also rewinds the draft's own
            # stale tail from the previous round)
            (dcache, last), drafts = jax.lax.scan(
                dstep, (set_index(dcache, idx), toks), None, length=k)
            # write the LAST draft token's KV too (its logits are dead
            # weight): at full acceptance the next round's frontier
            # sits right after it — without this write a perfect draft
            # attends to a hole and rejects its own continuation every
            # other round.  On partial acceptance the row is stale and
            # the usual rewind-overwrite invariant disposes of it.
            (dcache, _), _ = dstep((dcache, last), None)
            drafts = drafts.T                                   # [B, k]
            feed = jnp.concatenate([toks[:, None], drafts], axis=1)
            pos = idx[:, None] + jnp.arange(k + 1)[None, :]
            logits, mut = vmodel.apply(
                {"params": params, "cache": cache}, feed,
                positions=pos, mutable=["cache"])
            greedy = logits.argmax(-1).astype(jnp.int32)        # [B, k+1]
            match = (greedy[:, :k] == drafts).astype(jnp.int32)
            n_acc = jnp.cumprod(match, axis=1).sum(axis=1)      # [B]
            bonus = jnp.take_along_axis(greedy, n_acc[:, None], axis=1)
            j = jnp.arange(k + 1)[None, :]
            dpad = jnp.concatenate(
                [drafts, jnp.zeros((B, 1), jnp.int32)], axis=1)
            out = jnp.where(j < n_acc[:, None], dpad,
                            jnp.where(j == n_acc[:, None], bonus, 0))
            new_idx = idx + n_acc + 1
            return (set_index(mut["cache"], new_idx),
                    set_index(dcache, new_idx),
                    bonus[:, 0]), (out, n_acc + 1)

        (cache, draft_cache, last), (outs, counts) = jax.lax.scan(
            one_round, (cache, draft_cache, toks), None,
            length=self._spec_rounds)
        return cache, draft_cache, last, outs, counts

    def _finish_spec(self, toks: np.ndarray, counts: np.ndarray,
                     live: list) -> None:
        """Consume one speculative chunk: ``toks [R, slots, k+1]`` with
        ``counts[r, i]`` consumable tokens per round.  Same contract as
        :meth:`_finish_decode`, just ragged per round; and here alone
        the budgets follow the READ (``owed`` is what is left to read):
        how far a round got is the device's answer."""
        R = toks.shape[0]
        lane_tokens = R * (self._spec_k + 1)
        with self._stats_lock:
            self._lane_steps += len(self._slots) * lane_tokens
            self._active_lane_steps += len(live) * lane_tokens
        for i, _ in live:
            s = self._slots[i]
            with self._stats_lock:
                # device-side acceptance for the rate gauge: counts - 1
                # accepted drafts out of k proposed, per round
                self._spec_proposed += R * self._spec_k
                self._spec_accepted += int(counts[:, i].sum()) - R
            done = False
            for r in range(R):
                for t in range(int(counts[r, i])):
                    if s.remaining <= 0:
                        done = True
                        break
                    tok = int(toks[r, i, t])
                    s.emitted.append(tok)
                    s.remaining -= 1
                    if tok == self._eos or s.remaining == 0:
                        self._finish(i)
                        done = True
                        break
                if done:
                    break
            if not done:
                s.owed = s.remaining

    # -- the loop ------------------------------------------------------------
    def _loop(self) -> None:
        led = self._ledger
        t0 = time.perf_counter()
        while True:
            # a tick in flight or a mid-chunk admission is live work
            # even with no active slots and an empty queue — never
            # block on the queue then: idle_wait begins flushed
            block = (self._inflight is None and not self._any_active()
                     and not self._chunking)
            waiting = block and not self._pending and not self._tasks
            with led.phase("idle_wait" if waiting else "admit"):
                self._drain(block=block)
            if self._stopping:
                return  # stop() fails active slots + pending
            try:
                self._tick()
            except Exception as e:  # noqa: BLE001 — never die silently
                # a device error surfaces at a read, one tick after its
                # program was enqueued: both ticks in flight are lost,
                # and every request of either holds a slot (_admit)
                logger.exception("engine tick failed")
                self._inflight = None
                self._fail_all(e)
            # close the tick against the loop's own wall time (the
            # ledger takes idle_wait out of it): the phases must tile it
            t1 = time.perf_counter()
            with self._stats_lock:
                led.step_done(t1 - t0)
            t0 = t1

    def _drain(self, block: bool) -> None:
        """Pull queued requests into the host-side pending list; blocks
        for the first one only when the engine is otherwise idle."""
        while True:
            try:
                req = self._queue.get(block=block and not self._pending
                                      and not self._tasks
                                      and not self._stopping)
            except queue.Empty:
                return
            if req is None:                            # stop signal
                self._stopping = True
                return
            if isinstance(req, _Task):
                self._tasks.append(req)
            else:
                self._pending.append(req)
            block = False                              # drain non-blocking

    def _tick(self) -> None:
        """One engine tick: admit every consecutive prefix-reuse hit at
        the queue front plus at most ONE cold prefill group, then the
        decode chunk for the lanes that were already live, then the
        cache inserts — and only THEN read the tick before this one.
        The device always holds its next programs while the host reads,
        books and admits (a one-tick lookahead), so a tick lasts what
        its device programs last, not that plus the host's turn.
        Admission work per tick stays bounded by the free-slot count,
        so a burst of arrivals can never starve running lanes: they
        advance ``steps_per_sync`` tokens every tick regardless of the
        queue.

        What lets the host enqueue blind: the step's input tokens never
        leave the device (``self._toks``), and which slots are live
        follows from budgets (``_Slot.owed``): a slot whose budget ends
        inside the unread tick is not in this tick's mask, a slot
        admitted there is.  Only ``eos_id`` is data: an EOS is read one
        tick late, its slot has by then run one more program as live,
        and those ``steps_per_sync`` token steps are discarded
        (``lookahead_discarded_token_steps``); the emitted stream is the
        same.  A slot read as finished is free from the next ``_admit``
        on, so after a budget finish one tick later than a serial tick
        would free it: its commit needs the tokens this read delivers
        and has to be enqueued before an insert overwrites the slot.

        A speculative engine cannot know the next budgets (how far a
        round advances a slot is the device's answer), so it reads its
        own tick before the next: the same loop, the read not deferred.
        ``tasks`` see flushed state: the tick in flight is read first."""
        led = self._ledger
        if self._tasks:
            tick, self._inflight = self._inflight, None
            self._read(tick)
            with led.phase("tasks"):
                while self._tasks:
                    task = self._tasks.popleft()
                    try:
                        task.future.set_result(task.fn())
                    except BaseException as e:  # noqa: BLE001 — must resolve
                        task.future.set_exception(e)
        # the head's cause as this tick's admission begins: what the
        # tick before left it waiting for ("tick" for a new arrival)
        with led.phase("admit", pending=len(self._pending),
                       cause=(self._pending[0].cause if self._pending
                              else "none"),
                       lane_offset=(self._chunking[0].offset
                                    if self._chunking else -1)):
            live = [(i, s.request) for i, s in enumerate(self._slots)
                    if s.owed > 0]
            pres = self._admit(bool(live))
        with led.phase("dispatch", ahead=self._enqueued - self._ran):
            tick = self._dispatch(live, pres)
        if not self._reads_own:
            tick, self._inflight = self._inflight, tick
        self._read(tick)

    def _dispatch(self, live: list, pres: list[tuple]) -> "_Tick | None":
        """Enqueue the decode step for ``live`` and the inserts of
        ``pres`` behind it; nothing here waits for the device.  None
        where the tick has nothing to read later (idle, or only a mid
        chunk of a long admission, which ``_admit`` enqueued)."""
        if not live and not pres:
            return None
        tick = _Tick(live, pres=pres)
        if self._inflight is not None:    # never, on a speculative engine
            with self._stats_lock:
                self._lookahead_ticks += 1
        if live:
            if self._spec_k:
                (self._cache, self._draft_cache, self._toks, tick.dec,
                 tick.counts) = self._spec_jit(
                    self._cache, self._draft_cache, self._toks,
                    self._params, self._draft_params)
            elif self._block:
                self._rng, key = jax.random.split(self._rng)
                with obs_trace.annotation("engine/block_pass",
                                          live=len(live)):
                    (self._cache, self._toks, tick.dec, tick.counts,
                     tick.counters, tick.passes) = self._pass_jit(
                        self._cache, self._toks, key, self._params,
                        self._live_mask([i for i, _ in live]))
                self._passes_enqueued += self._T
                if not self._reads_own:
                    for i, _ in live:
                        self._schedule_passes(self._slots[i])
            else:
                self._rng, key = jax.random.split(self._rng)
                (self._cache, self._toks, tick.dec,
                 tick.counters) = self._step_jit(
                    self._cache, self._toks, key, self._params,
                    self._live_mask([i for i, _ in live]))
                for i, _ in live:
                    s = self._slots[i]
                    s.owed = max(0, s.owed - self._T)
            self._count_enqueue()
        # the read waits for the step and the admissions' prefills, not
        # for the inserts behind them
        tick.mark = self._enqueued
        for slab, toks, _, slots, reqs, lens, dslab, snap in pres:
            at = jnp.asarray(slots, jnp.int32)
            n = jnp.asarray(lens, jnp.int32)
            self._cache, self._toks = self._insert_jit(
                self._cache, self._toks, slab, at, n, toks)
            self._count_enqueue()
            if dslab is not None:
                self._draft_cache = self._draft_insert_jit(
                    self._draft_cache, dslab, at, n)
            # before the next step advances the slot's rings
            for lane, (slot, req) in enumerate(zip(slots, reqs)):
                self._snap_prompt(slot, req, lane, *snap)
        return tick

    def _read(self, tick: "_Tick | None") -> None:
        """Block on one tick's results and book them: tokens to slots
        and futures, finished slots' commits."""
        if tick is None:
            return
        led = self._ledger
        # single sync point for decode + every admission: the tokens
        # and, beside them, each program's counters vector
        with led.phase("sync"):
            dec, counters, counts, passes, firsts = jax.device_get(
                (tick.dec, tick.counters, tick.counts, tick.passes,
                 [(p[1], p[2]) for p in tick.pres]))
        self._ran = max(self._ran, tick.mark)
        with led.phase("finish"):
            if passes is not None:
                self._finish_blocks(dec, counts, tick.live, counters, passes)
            elif counts is not None:
                self._finish_spec(dec, counts, tick.live)
            elif dec is not None:
                self._finish_decode(dec, tick.live, counters)
            for pre, (ptoks, sown) in zip(tick.pres, firsts):
                self._finish_prefill(pre[3], pre[4], ptoks, sown)

    def _admit(self, lanes_live: bool) -> list[tuple]:
        """This tick's admissions, dispatched and not synced: every
        consecutive prefix hit at the queue front, then one chunk of
        each chunked admission in flight or else one cold group.
        Returns the in-flight tuples the tick inserts and, a tick
        later, finishes; their requests hold their slots from here on
        (``_fail_all`` and ``stop()`` find them there), with the budget
        the next ticks schedule from."""
        pres: list[tuple] = []

        def take(pre):
            if self._block:
                # what the insert places beside the slab: the first
                # blocks, the prompts' tails in them
                pre = (pre[0], self._first_blocks(pre[4], lanes_live),
                       *pre[2:])
            pres.append(pre)
            for slot, req in zip(pre[3], pre[4]):
                s = self._slots[slot]
                s.request, s.emitted = req, []
                # the prefill samples the first token; steps owe the rest
                s.remaining, s.owed = req.max_new, req.max_new - 1
                if self._block:
                    # every token comes out of a pass; ``owed`` counts
                    # block positions, the given ones with them
                    s.blocks, s.skip = [], len(req.given)
                    s.owed = s.skip + req.max_new
                    s.masked = self._block - s.skip
                    s.wait = s.idle = req.wait

        t0 = time.monotonic()
        # the slots chunked admissions hold while their requests are
        # not in them yet
        taken: set[int] = {st.slot for st in self._chunking}

        def hits():
            # drain consecutive front-of-queue prefix hits — each is a
            # cheap one-lane suffix prefill, and a shared-prefix burst
            # (the cache's own target traffic) must not serialize to
            # one admission per tick
            while True:
                reuse = self._next_reuse(taken)
                if reuse is None:
                    return
                pre = self._dispatch_reuse(*reuse)
                if pre is not None:
                    take(pre)

        hits()
        # long-prompt path: the chunked admissions in flight, which a
        # long prompt at the front joins while the lane has room (after
        # the hits between it and the one before it); each advances ONE
        # chunk per tick (a final chunk lands in pres and rides the
        # shared insert/finish path), displacing this tick's cold-group
        # slot in the dispatch budget
        lanes = self._chunk_lanes()
        while (len(self._chunking) < lanes
               and self._maybe_start_chunk(taken)):
            if len(self._chunking) < lanes:
                hits()
        lane_held, group = bool(self._chunking), None
        if lane_held:
            for pre in self._advance_chunks():
                take(pre)
        else:
            group = self._next_group(taken)
            if group is not None:
                pre = self._dispatch_prefill(*group)
                if pre is not None:
                    take(pre)
        if self._pending:
            self._passed_over(lane_held, group is not None, taken)
        if pres and lanes_live:
            with self._stats_lock:
                self._prefill_stall_s += time.monotonic() - t0
        return pres

    def _passed_over(self, lane_held: bool, grouped: bool,
                     taken: set[int]) -> None:
        """This tick's admission is over and requests are still
        pending: WHY, judged once for the queue's head and inherited by
        those behind it (the queue is FIFO).  ``slots``: no slot was
        free, so no policy of lanes would have admitted it; else
        ``lane``: the chunk lane was held this tick, which displaced
        the cold group and, full, bars the next long prompt; else ``group``:
        the tick's one cold group took another bucket or its cap; else
        ``tick``.  Each pending request is charged to its cause from
        its mark on; only a change of cause moves the mark."""
        if not self._free_slots(taken):
            cause = "slots"
        elif lane_held:
            cause = "lane"
        else:
            cause = "group" if grouped else "tick"
        now = time.monotonic()
        for req in self._pending:
            if req.cause != cause:
                req.waits[req.cause] = (req.waits.get(req.cause, 0.0)
                                        + now - req.t_mark)
                req.cause, req.t_mark = cause, now

    def _fail_all(self, e: Exception) -> None:
        n = 0
        for s in self._slots:
            if s.request is not None:
                s.request.future.set_exception(e)
                s.request, s.owed = None, 0
                n += 1
        for st in self._chunking:
            st.req.future.set_exception(e)
            n += 1
        self._chunking = []
        with self._stats_lock:
            self._failed_requests += n

    def _any_active(self) -> bool:
        return any(not s.free for s in self._slots)

    def _free_slots(self, taken: set[int]) -> list[int]:
        """Free slots but ``taken`` (those chunked admissions hold
        while their requests are not in them yet)."""
        return [i for i, s in enumerate(self._slots)
                if s.free and i not in taken]

    def _bucket(self, n: int) -> int:
        """Smallest prefill bucket holding an n-token prompt (buckets
        extend to cache_len at construction, so any prompt submit()
        accepts has one)."""
        return next(b for b in self._buckets if n <= b)

    def _next_group(self, taken: set[int] = frozenset()
                    ) -> tuple[int, list[int], list[_Request]] | None:
        """Take the next same-bucket run of pending requests (FIFO from
        the front) as one prefill group, capped by free slots (minus
        ``taken``, the slot a chunked admission holds) and the largest
        PREFILL_KS sub-batch size (compile count stays bounded at
        buckets × |PREFILL_KS|)."""
        if self._stopping or not self._pending:
            return None
        free = self._free_slots(taken)
        if not free:
            return None
        P = self._bucket(len(self._pending[0].ids))
        reqs: list[_Request] = []
        cap = min(len(free), self.PREFILL_KS[0])
        while (self._pending and len(reqs) < cap
               and self._bucket(len(self._pending[0].ids)) == P):
            reqs.append(self._pending.popleft())
        K = next(k for k in self.PREFILL_KS if k <= len(reqs))
        for req in reversed(reqs[K:]):                 # overflow back, FIFO
            self._pending.appendleft(req)
        reqs = reqs[:K]
        self._stamp_admit(reqs, "cold")
        return P, free[:K], reqs

    def _stamp_admit(self, reqs: list[_Request], lane: str) -> None:
        """The requests left ``_pending`` for an admission down ``lane``
        (cold group, prefix reuse or chunked): their queue wait ends
        here, its last stretch charged to the cause they carried."""
        now = time.monotonic()
        with self._stats_lock:
            for req in reqs:
                req.lane, req.t_admit = lane, now
                req.waits[req.cause] = (req.waits.get(req.cause, 0.0)
                                        + now - req.t_mark)
                self._stage(req, "queue_wait")
                for cause, s in req.waits.items():
                    self._wait_cause_s[cause] += s

    def _dispatch_prefill(self, P: int, slots: list[int],
                          reqs: list[_Request]):
        """Dispatch (not sync) one prefill group; returns the in-flight
        device values or None when tracing/dispatch failed (that group's
        futures are failed here; device-side errors surface at the tick
        sync)."""
        K = len(reqs)
        if self._kv is not None:
            self._kv_misses += K
            self._prefill_tokens += sum(len(r.ids) for r in reqs)
        try:
            ids = np.zeros((K, P), np.int32)
            lens = np.zeros((K,), np.int32)
            for i, req in enumerate(reqs):
                ids[i, :len(req.ids)] = req.ids
                lens[i] = len(req.ids)
            self._rng, key = jax.random.split(self._rng)
            ends = (jnp.asarray([self._snap_end(r) for r in reqs], jnp.int32)
                    if self._recurrent else None)
            slab, toks, sown, snap = self._prefill_fn(P, K)(
                self._params, jnp.asarray(ids), jnp.asarray(lens), key, ends)
            self._count_enqueue()
            self._counters.on_prefill(K, P, int(lens.sum()),
                                      lens=lens.tolist())
            dslab = (self._draft_prefill_fn(P, K)(
                self._draft_params, jnp.asarray(ids), jnp.asarray(lens))
                if self._spec_k else None)
            return slab, toks, sown, slots, reqs, lens, dslab, (snap, 0)
        except Exception as e:  # noqa: BLE001 — fail THIS group only
            logger.exception("prefill failed (bucket %d, %d reqs)", P, K)
            for req in reqs:
                req.future.set_exception(e)
            with self._stats_lock:
                self._failed_requests += len(reqs)
            return None

    # -- chunked prefill (long admissions) -----------------------------------
    def _chunk_lanes(self) -> int:
        """How many chunked admissions the lane holds at once: two where
        the prefill ladder the fit left (``_require_fit``) has room for
        two lanes of a chunk's length, else one.  On the chip a chunk of
        256 rows is bound by its arithmetic, so two prompts' chunks cost
        the device two chunks whatever program runs them; what two in
        the lane share is the tick, whose token steps carry 512 prompt
        tokens where they carried 256 (PERF.md section 6, PR 54)."""
        return min(2, self.PREFILL_KS[0])

    def _maybe_start_chunk(self, taken: set) -> bool:
        """Claim the front pending request as a CHUNKED admission when
        its prompt exceeds the chunk size: the prompt prefills
        ``prefill_chunk`` tokens per tick into a private one-lane slab,
        interleaved with every decode dispatch, so a long admission
        costs live lanes one chunk of stall per tick instead of one
        monolithic prefill (doc/serving.md "Chunked prefill").  Its slot
        joins ``taken``; whether one was claimed."""
        C = self._chunk_tokens
        if not C or self._stopping or not self._pending:
            return False
        n = len(self._pending[0].ids)
        if n <= C:
            return False
        # the final chunk pads to its suffix bucket and its cache write
        # is a CLAMPED dynamic_update_slice (transformer.py) — if
        # offset + bucket overhangs the cache it would shift backwards
        # over the already-prefilled prefix.  Prompts that close to the
        # cache cap fall back to the monolithic prefill, which always
        # fits by submit()'s bound.
        off = C * ((n - 1) // C)
        if off + self._bucket(n - off) > self._dcfg.max_len:
            return False
        free = self._free_slots(taken)
        if not free:
            return False
        slot, req = free[0], self._pending.popleft()
        taken.add(slot)
        self._stamp_admit([req], "chunk")
        if self._kv is not None:
            # one admission, counted once at start (the reuse matcher
            # already passed on it — this is the cold long-prompt path)
            self._kv_misses += 1
            self._prefill_tokens += len(req.ids)
        if not self._chunking:
            self._lane_t0 = req.t_admit
        self._chunking.append(
            _ChunkState(req, slot, 0, *self._chunk_start()))
        with self._stats_lock:
            self._chunked_admissions += 1
        return True

    def _chunk_start(self):
        """``(slab, sown)`` a chunked admission starts from: a zeroed
        one-lane cache and its counters accumulator, out of one compiled
        program — nothing traced, one dispatch.  On a mesh the
        accumulator is born where the chunk programs return it (after
        a host-made scalar, the first admission's second chunk re-traced
        and re-compiled ``mid`` in the middle of traffic)."""
        return self._zeros(
            ("chunk_start",), (self._cache_shapes(1), self._acc_shape),
            (self._cache_shardings(1), self._rep))

    def _advance_chunks(self) -> list[tuple]:
        """Dispatch ONE chunk of every chunked admission in flight (no
        sync); the in-flight tuples of those whose chunk was their
        last."""
        pres = [self._advance_chunk(st) for st in list(self._chunking)]
        if len(pres) == 2:
            with self._stats_lock:
                self._chunk_pair_dispatches += 1
        return [pre for pre in pres if pre is not None]

    def _leave_lane(self, st: _ChunkState) -> None:
        """``st``'s last chunk is dispatched, or it failed: the lane is
        free of it from here on, and its held time ends with the last
        admission in it."""
        self._chunking.remove(st)
        if not self._chunking:
            with self._stats_lock:
                self._chunk_lane_busy_s += time.monotonic() - self._lane_t0

    def _advance_chunk(self, st: _ChunkState):
        """Dispatch ONE chunk of the chunked admission ``st`` (no
        sync).  Mid chunks write straight into the private slab — the
        slab's own cache_index tracks the offset, so every mid chunk of
        one size shares one executable.  The final chunk pads to its
        suffix bucket, samples the first token, and returns the same
        in-flight tuple as :meth:`_dispatch_prefill`, so insert/finish/
        commit are the shared path."""
        ids, C = st.req.ids, self._chunk_tokens
        rest = len(ids) - st.offset
        st.req.chunks += 1
        try:
            if rest > C:
                chunk = np.asarray(ids[st.offset:st.offset + C])[None, :]
                st.slab, st.sown = self._chunk_mid_fn(C)(
                    self._params, st.slab, jnp.asarray(chunk), st.sown)
                self._count_enqueue()
                self._counters.on_prefill(1, C, C, st.offset, final=False)
                st.offset += C
                with self._stats_lock:
                    self._prefill_chunks += 1
                return None
            P = self._bucket(rest)
            tail = np.zeros((1, P), np.int32)
            tail[0, :rest] = ids[st.offset:]
            self._rng, key = jax.random.split(self._rng)
            at = (jnp.asarray([max(self._snap_end(st.req) - st.offset, 0)],
                              jnp.int32) if self._recurrent else None)
            slab, toks, sown, snap = self._chunk_final_fn(P)(
                self._params, st.slab, jnp.asarray(tail),
                jnp.asarray([rest], jnp.int32), st.sown, key, at)
            self._count_enqueue()
            self._leave_lane(st)
            self._counters.on_prefill(1, P, rest, st.offset)
            with self._stats_lock:
                self._prefill_chunks += 1
            dslab = self._draft_slab_for(st.req) if self._spec_k else None
            return (slab, toks, sown, [st.slot], [st.req], [len(ids)],
                    dslab, (snap, st.offset))
        except Exception as e:  # noqa: BLE001 — fail THIS request only
            logger.exception("chunked prefill failed (offset %d of %d)",
                             st.offset, len(ids))
            st.req.future.set_exception(e)
            if st in self._chunking:
                self._leave_lane(st)
            with self._stats_lock:
                self._failed_requests += 1
            return None

    @_compiled("chunk", lambda C: ("chunk", C))
    def _chunk_mid_fn(self, C: int):
        """Compiled per chunk size: advance a one-lane prefill slab by
        C prompt tokens (every token real — the only padded chunk is
        the final one, which is a bucketed suffix prefill)."""
        model = self._model

        def mid(params, slab, ids, sown_in):
            idx = self._positions(slab)           # == tokens prefilled
            _, mut = model.apply(
                {"params": params, "cache": slab}, ids,
                positions=idx[:, None] + jnp.arange(C)[None, :],
                mutable=["cache", "intermediates"],
                **({"tail": False} if self._cut else {}))
            return mut["cache"], sown_in + self._sown(mut)

        return jax.jit(mid, donate_argnums=(1,), out_shardings=(
            self._cache_shardings(1), self._rep))

    @_compiled("chunkfin", lambda P: ("chunkfin", P))
    def _chunk_final_fn(self, P: int):
        """Compiled per suffix bucket: the last chunk — bucketed,
        token-masked, sampled at the prompt's true last position."""
        model = self._model

        def fin(params, slab, ids, rel_lens, sown_in, key, snap_at=None):
            idx = self._positions(slab)
            logits, mut = model.apply(
                {"params": params, "cache": slab}, ids,
                positions=idx[:, None] + jnp.arange(P)[None, :],
                token_mask=jnp.arange(P)[None, :] < rel_lens[:, None],
                snap_at=snap_at, mutable=_PREFILL_MUTABLE,
                **self._last(rel_lens - 1))
            toks = self._first(logits, rel_lens - 1, key)
            return (mut["cache"], toks, sown_in + self._sown(mut),
                    self._snap_of(mut))

        return jax.jit(fin, donate_argnums=(1,), out_shardings=(
            self._cache_shardings(1), self._rep, self._rep, None))

    # -- prefix reuse (paged KV engines only) --------------------------------
    def _next_reuse(self, taken: set[int] = frozenset()
                    ) -> tuple[int, "_Request", list] | None:
        """If the FRONT pending request extends a committed chain, take
        it as a one-lane reuse admission (FIFO preserved: a miss at the
        front falls through to the group path unchanged).  ``taken``
        excludes the slot a chunked admission holds."""
        if self._kv is None or not self._reuse:
            return None
        if self._stopping or not self._pending:
            return None
        free = self._free_slots(taken)
        if not free:
            return None
        req0 = self._pending[0]
        chain = self._kv.match(req0.ids)
        if self._recurrent:
            req0.cut = self._kv.last_cut
        cache_len = self._dcfg.max_len
        while chain:
            # the suffix pads to its bucket, and the cache write is a
            # CLAMPED dynamic_update_slice (transformer.py) — an
            # overhanging slab would silently shift backwards over the
            # gathered prefix and poison the pool at commit.  Shorten
            # the chain until prefix + suffix bucket fits; n=0 is the
            # cold path, which always fits by construction.
            prefix = len(chain) * self._kv.block
            if prefix + self._bucket(len(req0.ids) - prefix) <= cache_len:
                break
            chain = self._kv.shorter(chain)
        if not chain:
            return None
        req = self._pending.popleft()
        self._stamp_admit([req], "reuse")
        return free[0], req, chain

    def _pad_blocks(self, n: int) -> int:
        """A chain of ``n`` blocks as the reuse programs take it: the
        next power of two, capped at the blocks a cache holds."""
        return min(1 << max(0, (n - 1).bit_length()),
                   self._dcfg.max_len // self._kv.block)

    def _dispatch_reuse(self, slot: int, req: "_Request", chain: list):
        """Dispatch one prefix-hit admission: gather the chain's blocks
        into a fresh one-lane slab and prefill ONLY the suffix (the
        skipped prefix is the whole point — its logits were already
        paid for by whoever committed the chain).  Returns the same
        in-flight tuple shape as :meth:`_dispatch_prefill` so the tick's
        insert/finish path is shared."""
        n = len(chain)
        prefix_len = n * self._kv.block
        suffix = req.ids[prefix_len:]
        P = self._bucket(len(suffix))
        self._kv_hits += 1
        self._prefill_tokens += len(req.ids)
        self._prefill_tokens_skipped += prefix_len
        req.skipped = prefix_len
        try:
            ids = np.zeros((1, P), np.int32)
            ids[0, :len(suffix)] = suffix
            # chain length pads to a power of two (capped at the cache)
            # with the reserved scratch block, so the compile family is
            # buckets x log2(blocks-per-cache), not one per depth — a
            # growing conversation must not stall every live lane on a
            # fresh XLA compile each turn.  The padded zeros land
            # beyond prefix_len and are overwritten or masked before
            # any query can attend them.
            n_pad = self._pad_blocks(n)
            block_ids = np.zeros((n_pad,), np.int32)
            block_ids[:n] = [nd.block_id for nd in chain]
            self._rng, key = jax.random.split(self._rng)
            n_real = jnp.asarray([len(suffix)], jnp.int32)
            snap_id = self._kv.snap_arg(chain[-1].snap)
            hit = (self._kv.pool, jnp.asarray(block_ids),
                   jnp.asarray(prefix_len, jnp.int32))
            # what the hit brings back into a lane: the chain's blocks
            # and, for a state class, one snapshot (the span is the
            # host's enqueue; the device's part is the ``load`` program,
            # or the fused prefill's gather)
            reattach = obs_trace.annotation(
                "engine/reattach", blocks=n, padded=n_pad,
                bytes=n * self._kv.block_bytes,
                snapshot=int(bool(chain[-1].snap)))
            if self._recurrent:
                # two programs where the others fuse them: the gather
                # alone (small: one attention layer's blocks and the
                # snapshot), then the chunk lane's last-chunk program,
                # which IS a suffix prefill from a slab's state.  Fused,
                # every (suffix bucket, chain depth) pair would be one
                # more compile of the whole stack (36 of them at 4096
                # tokens: nine minutes of set-up on the chip, PR 32).
                # The others keep the fused program: as a pair, their
                # sessions cell read 1.4% fewer tokens a second, lower
                # in four pairs of four (PERF.md section 6, PR 32)
                at = jnp.asarray([max(self._snap_end(req) - prefix_len, 0)],
                                 jnp.int32)
                with reattach:
                    lane = self._load_prefix_fn(n_pad)(*hit, snap_id)
                slab, toks, sown, snap = self._chunk_final_fn(P)(
                    self._params, lane, jnp.asarray(ids), n_real,
                    self._zeros(("acc",), self._acc_shape, None),
                    key, at)
            else:
                with reattach:
                    slab, toks, sown, snap = self._reuse_prefill_fn(
                        P, n_pad)(self._params, *hit, jnp.asarray(ids),
                                  n_real, key, snap_id)
            self._count_enqueue()
            self._counters.on_prefill(1, P, len(suffix), prefix_len)
            # insert true_lens = the FULL prompt length: the slab's
            # cache_index already sits at prefix+suffix and the pool
            # lane must agree.  The draft has no pool: its slab is
            # rebuilt from the FULL prompt in one small-model pass
            # (draft state moves the accept rate, never correctness).
            dslab = self._draft_slab_for(req) if self._spec_k else None
            return (slab, toks, sown, [slot], [req], [len(req.ids)], dslab,
                    (snap, prefix_len))
        except Exception as e:  # noqa: BLE001 — fail THIS request only
            logger.exception("reuse prefill failed (suffix bucket %d, "
                             "%d blocks)", P, n)
            req.future.set_exception(e)
            with self._stats_lock:
                self._failed_requests += 1
            return None

    @_compiled("reuse", lambda P, n_pad: ("reuse", P, n_pad))
    def _reuse_prefill_fn(self, P: int, n_pad: int):
        """Compiled per (suffix bucket, PADDED chain length): fused
        gather-prefix + suffix prefill + sample.  ``prefix_len`` (the
        real chain length in tokens, <= ``n_pad * block``) rides as a
        traced scalar so every chain depth in a padding bucket shares
        one executable."""
        model = self._model
        kv = self._kv

        def prefill(params, pool, block_ids, prefix_len, ids, true_lens,
                    key, snap_id):
            cache = _zeros_of(self._cache_shapes(1))
            cache = kv.load_prefix_into(cache, pool, block_ids, n_pad,
                                        prefix_len, snap_id)
            logits, mut = model.apply(
                {"params": params, "cache": cache}, ids,
                positions=prefix_len
                + jnp.broadcast_to(jnp.arange(P), ids.shape),
                token_mask=jnp.arange(P)[None, :] < true_lens[:, None],
                mutable=["cache", "intermediates"],
                **self._last(true_lens - 1))
            toks = self._first(logits, true_lens - 1, key)
            return mut["cache"], toks, self._sown(mut), None

        return jax.jit(prefill)

    @_compiled("load", lambda n_pad: ("load", n_pad))
    def _load_prefix_fn(self, n_pad: int):
        """Compiled per PADDED chain length: a fresh one-lane slab with
        a hit's blocks and layer-state snapshot in it, its index at the
        prefix's end (``PagedKVCache.load_prefix_into`` alone): what a
        state-space configuration's reuse admission runs before the
        last-chunk program (``_dispatch_reuse``)."""
        kv = self._kv

        def load(pool, block_ids, prefix_len, snap_id):
            return kv.load_prefix_into(_zeros_of(self._cache_shapes(1)), pool,
                                       block_ids, n_pad, prefix_len, snap_id)

        return jax.jit(load)

    def _finish_prefill(self, slots: list[int], reqs: list[_Request],
                        toks: np.ndarray, sown: np.ndarray) -> None:
        """``sown``: what the admission's programs sowed (a chunked
        one's, all its chunks)."""
        now = time.monotonic()
        with self._stats_lock:
            for req in reqs if not self._block else ():
                req.t_first = now     # its first token is on the host
                self._stage(req, "prefill")
                self._stage(req, "ttft")
            self._counters.read(
                sown, sum(len(r.ids) - r.skipped for r in reqs), decode=False)
        if self._block:       # the first block's commit brings the first
            return
        for slot, tok in zip(slots, toks.tolist()):
            s = self._slots[slot]         # the request's since _admit
            s.emitted = [tok]
            s.remaining -= 1
            if s.remaining == 0 or tok == self._eos:
                self._finish(slot)

    def _snap_prompt(self, slot: int, req: "_Request", lane: int = 0,
                     snap=None, base: int = 0) -> None:
        """The window layers' state at the end of the PROMPT, while the
        slot's rings still hold it: enqueued right behind the insert,
        before the next tick's step advances them.  It needs nothing the
        device returns.  The last window before the deepest block edge
        the same prompt can match again, ``(len - 1) // block`` blocks
        down.  Without it only a continuation of prompt + answer could
        start from the pool; with it a prompt that comes again does.
        One small dispatch an admission; the commit gives the snapshot
        its node.

        A state-space layer's state at that edge cannot come out of the
        slot, which is at the prompt's END: the prefill's last program
        computed it on its way (``snap``, lane ``lane``; the program
        began at position ``base``).  An edge before ``base`` was
        passed a program ago and is not snapshotted (counted)."""
        if self._kv is None or not self._snapped:
            return
        end = self._snap_end(req)
        if end <= req.skipped:     # nothing new: the hit's own snapshot
            return
        sid = 0 if end < base else self._kv.snap_alloc(req.session)
        if not sid:
            self._state_snap_skips += self._recurrent
            return
        if self._ringed:
            self._kv.store_blocks(self._cache, slot, 0, [], (sid, end))
        if self._recurrent:
            self._kv.store_state(snap, lane, sid)
        req.snap = (sid, end)

    def _live_mask(self, active: list[int]):
        """The decode step's ``live`` argument: [slots] bool."""
        live = np.zeros((len(self._slots),), bool)
        live[active] = True
        return jnp.asarray(live)

    def _finish_decode(self, toks: np.ndarray, live: list,
                       sown: np.ndarray) -> None:
        """Consume one decode chunk [slots, T] for the (slot, request)
        pairs that were ``live`` in it.  A slot that no longer holds
        its request ended at an EOS while this program was already
        enqueued: its token steps are discarded.  ``sown``: what the
        program's layers sowed (``_sown``)."""
        T, cap = self._T, self._dcfg.max_len
        mine = [(i, self._slots[i]) for i, req in live
                if self._slots[i].request is req]
        # step t of the program read a live slot up to the token it
        # appended: prompt + emitted so far + t positions
        held = [min(len(s.request.ids) + len(s.emitted) + t, cap)
                for _, s in mine for t in range(T)]
        with self._stats_lock:
            self._lane_steps += len(self._slots) * T
            self._active_lane_steps += len(live) * T
            self._lookahead_discarded += (len(live) - len(mine)) * T
            self._counters.on_decode(held, len(live), T)
            self._counters.read(sown, len(live) * T, decode=True)
        for i, s in mine:         # live, so it had tokens left to read
            for t in range(T):
                tok = int(toks[i, t])
                s.emitted.append(tok)
                s.remaining -= 1
                if tok == self._eos or s.remaining <= 0:
                    self._finish(i)
                    break

    def _finish(self, slot: int) -> None:
        s = self._slots[slot]
        req = s.request
        assert req is not None
        out = np.asarray(s.emitted, np.int32)
        if self._eos is not None and self._eos in s.emitted:
            out = out[:s.emitted.index(self._eos) + 1]
        if self._kv is not None:
            try:
                with self._ledger.phase("kv_commit"):
                    self._kv_commit(slot, req, s.emitted)
            except Exception:  # noqa: BLE001 — the cache is an accelerator
                logger.exception("kv commit failed for slot %d (request "
                                 "unaffected)", slot)
        req.t_done = time.monotonic()
        n_out = len(out)
        decode_s = req.stage_s("decode")
        # an answer whose first read brought all of it (one token; one
        # block) has no gap between tokens
        later = n_out - req.n_first
        if later > 0:
            _INTERTOKEN_SECONDS.observe(decode_s / later)
        with self._stats_lock:
            self._done_requests += 1
            self._emitted_tokens += n_out
            self._stage(req, "decode")
            if later > 0:
                self._decode_tokens += later
                self._decode_s += decode_s
        s.request, s.owed = None, 0
        s.emitted = []
        if obs_trace.active():
            self._emit_request(req, n_out)
        req.future.set_result(out)

    @staticmethod
    def _emit_request(req: "_Request", n_out: int) -> None:
        """One ``engine/request`` event per finished request, pinned
        under the submitter's span (``ReplicaServer.serve_submit`` runs
        in the gateway's trace), so a merged timeline reads
        gateway/request > gateway/route > serving/submit >
        engine/request > serving/complete.  It says what THIS request
        waited for: its stages, its lane and chunks, and its queue wait
        by cause (``wait_<cause>``, those it was charged to)."""
        ids = {}
        if req.ctx is not None:
            child = req.ctx.child()
            ids = {"trace_id": child.trace_id, "span_id": child.span_id,
                   "parent_id": child.parent_id}
        # what the ledger was given (``_Request.stage_s``): the three
        # stages tile the request's life
        tiled = {k: req.stage_s(k) for k in TILING_STAGES}
        dur = sum(tiled.values())
        obs_trace.emit(
            "engine/request", dur=dur,
            # edl-lint: disable=clock — back-dating a TRACE ts to the
            # span begin (merge convention: ts is begin)
            at=time.time() - dur,
            **{k: round(v, 6) for k, v in tiled.items()},
            lane=req.lane, chunks=req.chunks,
            **{f"wait_{c}": round(s, 6) for c, s in req.waits.items()},
            n_prompt=len(req.ids) + len(req.given), n_out=n_out,
            prefix_tokens_skipped=req.skipped, **ids)

    def _kv_commit(self, slot: int, req: "_Request",
                   emitted: list[int]) -> None:
        """Persist the finished lane's full KV blocks into the pool and
        pin the chain for the request's session.  The lane holds KV for
        every PROCESSED token — the prompt plus every emitted token that
        was fed back — so the committed sequence is
        ``prompt + emitted[:-1]`` (the final sampled token was never
        re-embedded; its KV does not exist)."""
        seq = np.concatenate([req.ids,
                              np.asarray(emitted[:-1], np.int32)])
        if self._block:
            # rows enter a slot at a commit pass only: the prefilled rows
            # and every committed block, its cut end with it
            seq = np.concatenate([req.ids, np.asarray(
                self._slots[slot].blocks, np.int32)])
        start_block, new_ids, tail = self._kv.commit(seq)
        sid, at = req.snap
        chain, depth = self._kv.committed, at // self._kv.block
        self._kv.snap_attach(
            chain[depth - 1] if 0 < depth <= len(chain) else None, sid)
        snap = (0, 0)
        self._state_reprefill += req.cut
        # a recurrence has moved past the tail's end and kept nothing
        # of it: with a recurrent layer an answer's end is never
        # snapshotted, and a session's next turn starts from the edge
        # its last prompt left (kv_state_reprefill_tokens)
        if self._ringed and not self._recurrent and tail is not None:
            # the window layers' last window before the tail's end, as
            # the slot's rings still hold it: the slot may have run
            # self._overrun token steps past the request's end, each
            # overwriting the oldest ring position
            end = (start_block + len(new_ids)) * self._kv.block
            oldest = len(seq) + self._overrun - self._dcfg.ring_len
            if max(0, end - self._kv.window) >= oldest:
                snap = (self._kv.snap_for(tail), end)
        self._kv.store_blocks(self._cache, slot, start_block, new_ids, snap)
        if req.session is not None and tail is not None:
            self._kv.pin_session(req.session, tail)
