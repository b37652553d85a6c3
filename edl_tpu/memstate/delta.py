"""Delta replication plane: sub-checkpoint-loss failover from streamed
optimizer-state deltas (ROADMAP item 3, ISSUE 17).

Between "checkpoint" (durable, minutes apart) and "live state" (gone
with the pod) this module adds a third tier: every
``EDL_TPU_DELTA_EVERY`` steps each trainer process host-snapshots its
shards, diffs the CRCs against the last sealed record, and pushes only
the CHANGED shard bytes — off the step path, on one worker thread — to
its own pod's cache service AND the pod's consistent-hash ring replica
(placement.replica_for), over the exact same chunked/streaming RPC
plane full shard-sets use.  A crash then loses at most one delta
interval of work instead of a checkpoint interval.

Chain format
------------
A *chain* is identified by ``(owner pod, src)`` where ``src`` is the
producing process index — every trainer process owns the shards it
pushes (replica_id == 0 dedup, same rule as the full-set tee).  Records
link hash-to-hash from an anchor derived from the base step (the last
committed checkpoint the diff is against):

    prev_0  = sha1("edl-delta-anchor:<base_step>")
    hash_i  = sha1(prev_{i-1}, step_i, seq_i,
                   sorted (key, crc32, nbytes) of the record's manifest)

so a verifier can detect a torn chain (missing / reordered / replaced
record, or a manifest that does not match its hash) with no trust in
the holder, and per-shard CRCs guard the payload bytes themselves.
Record payloads stage through the ordinary ``cache_put_chunk`` /
``cache_fetch`` / ``cache_fetch_stream`` surface under a reserved
*wire-owner* namespace — ``~delta:<owner>:<src>:<seq>`` — which the
service resolves internally; no new transfer RPCs exist.

Freshest-recoverable selection
------------------------------
A step F is recoverable iff EVERY producer chain of the committed base
has an intact record at exactly F (records are cumulative diffs, so a
producer's shards can only be reconstructed at its own record steps),
and the number of observed producers matches the process count the
records claim — a producer whose chain was lost entirely must demote
the answer, never silently produce a torn mix of steps.  The overlay
for F is then: base full set, patched by each chain's records in seq
order up to F.  Any break falls back chain -> peer-full -> Orbax.

Chains are bounded by ``EDL_TPU_DELTA_MAX_CHAIN`` (the producer stops
staging when the cap is hit — freshness saturates until the next
checkpoint) and compacted into the base on each checkpoint commit: a
newly committed full set at step S subsumes every chain with an older
base, and the producer re-anchors (``rebase``) on every save.
"""

from __future__ import annotations

import hashlib
import json
import queue
import threading
import time

from edl_tpu.memstate import advert, placement, shards
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.utils import constants
from edl_tpu.utils.logger import get_logger

logger = get_logger(__name__)

_RECORDS = obs_metrics.counter(
    "edl_delta_records_total",
    "Delta records produced by this process, by result", ("result",))
_BYTES = obs_metrics.counter(
    "edl_delta_bytes_total",
    "Changed-shard payload bytes pushed into delta chains")
_LAG = obs_metrics.histogram(
    "edl_delta_lag_seconds",
    "Snapshot-to-sealed replication lag per delta record",
    buckets=obs_metrics.DEFAULT_BUCKETS)
_LAG_STEPS = obs_metrics.gauge(
    "edl_delta_lag_steps",
    "Steps between the live train step and the last sealed delta record")
_CHAIN_LEN = obs_metrics.gauge(
    "edl_delta_chain_len",
    "Records in this producer's current delta chain")
_BREAKS = obs_metrics.counter(
    "edl_delta_chain_breaks_total",
    "Delta chain breaks detected (push failures, commit rejects, "
    "verification failures), by reason", ("reason",))
_RESIDENT = obs_metrics.gauge(
    "edl_delta_bytes_resident",
    "Bytes resident in delta chains on this pod's cache service")

# reserved owner namespace delta record payloads ride the ordinary
# cache_put_chunk/cache_fetch wire under ('~' cannot start a pod id)
WIRE_PREFIX = "~delta:"


def resident_gauge():
    """The resident-chain-bytes gauge — set by the cache service (which
    holds the chains) but registered here with the rest of edl_delta_*."""
    return _RESIDENT


def anchor_hash(base_step: int) -> str:
    """The chain anchor: prev_hash of a chain's first record."""
    return hashlib.sha1(
        f"edl-delta-anchor:{int(base_step)}".encode()).hexdigest()


def chain_hash(prev_hash: str, step: int, seq: int, manifest: dict) -> str:
    """Hash of one record, linking ``prev_hash``: covers the step, the
    seq, and the record manifest's (key, crc, nbytes) triples — the
    payload bytes are covered transitively through the CRCs."""
    body = json.dumps(
        [prev_hash, int(step), int(seq),
         sorted((k, int(e["crc"]), int(e["nbytes"]))
                for k, e in manifest.items())],
        separators=(",", ":"))
    return hashlib.sha1(body.encode()).hexdigest()


def wire_owner(owner: str, src: str, seq: int) -> str:
    """The staged/fetch owner string one record's payload lives under."""
    return f"{WIRE_PREFIX}{owner}:{src}:{int(seq)}"


def parse_wire_owner(s: str):
    """``(owner, src, seq)`` for a delta wire-owner string, else None."""
    if not isinstance(s, str) or not s.startswith(WIRE_PREFIX):
        return None
    try:
        owner, src, seq = s[len(WIRE_PREFIX):].rsplit(":", 2)
        return owner, src, int(seq)
    except ValueError:
        return None


def intact_prefix(base_step: int, records: list) -> list:
    """The longest verified prefix of ``records``: seq contiguous from
    1, steps strictly increasing past the base, every prev/hash link
    recomputed from the record's own manifest.  A mid-list break (a
    torn chain) is counted; a list that simply ends is not a break."""
    prev = anchor_hash(base_step)
    nseq, last_step = 1, int(base_step)
    out = []
    for rec in sorted(records or [], key=lambda r: int(r.get("seq", 0))):
        step, seq = int(rec.get("step", -1)), int(rec.get("seq", -1))
        if (seq != nseq or step <= last_step
                or rec.get("prev") != prev
                or chain_hash(prev, step, seq,
                              rec.get("shards") or {}) != rec.get("hash")):
            _BREAKS.labels(reason="torn").inc()
            break
        out.append(rec)
        prev, nseq, last_step = rec["hash"], nseq + 1, step
    return out


def plan_freshest(committed: int, listings: dict, max_step: int | None = None):
    """The freshest recoverable overlay over the ``committed`` base.

    ``listings``: ``{pod: cache_delta_manifest()}`` from every reachable
    holder.  Returns ``None`` (no overlay — restore the plain base) or
    ``{"step": F, "overlay": {key: (ent, [(pod, ent, wire_owner)])},
    "meta": [(pod, wire_owner)]}`` where the overlay candidates REPLACE
    the base candidates for their keys and ``meta`` lists holders of
    the step-F sidecar.  ``max_step`` bounds F (multi-process restores
    agree on a target first, then each process plans toward it)."""
    producers: dict[tuple, dict[int, list]] = {}
    nproc_at: dict[int, int] = {}
    for pod, listing in (listings or {}).items():
        for ch in (listing or {}).values():
            if int(ch.get("base_step", -1)) != int(committed):
                continue
            pkey = (str(ch.get("owner")), str(ch.get("src", "0")))
            by = producers.setdefault(pkey, {})
            for rec in intact_prefix(committed, ch.get("records")):
                step = int(rec["step"])
                if max_step is not None and step > int(max_step):
                    break
                by.setdefault(step, []).append((pod, rec))
                n = int(rec.get("nproc") or 0)
                if n:
                    nproc_at[step] = max(nproc_at.get(step, 0), n)
    producers = {p: by for p, by in producers.items() if by}
    if not producers:
        return None
    # a recoverable cut needs an intact record from EVERY producer at
    # exactly F, and the producer count must match the world size the
    # records claim — a chain lost on every holder demotes the answer
    # rather than mixing shard bytes from different steps
    target = None
    for step in sorted({s for by in producers.values() for s in by},
                       reverse=True):
        want = nproc_at.get(step, 0) or len(producers)
        if len(producers) == want and all(step in by
                                          for by in producers.values()):
            target = step
            break
    if target is None:
        _BREAKS.labels(reason="no_cut").inc()
        return None
    overlay: dict[str, tuple] = {}
    meta_srcs: list[tuple[str, str]] = []
    for (owner, src), by in producers.items():
        for step in sorted(by):
            if step > target:
                break
            recs = by[step]
            w = wire_owner(owner, src, int(recs[0][1]["seq"]))
            for key, ent in (recs[0][1].get("shards") or {}).items():
                overlay[key] = (ent, [(pod, ent, w) for pod, _r in recs])
            if step == target:
                meta_srcs.extend((pod, w) for pod, rec in recs
                                 if rec.get("has_meta"))
    return {"step": target, "overlay": overlay, "meta": meta_srcs}


def probe_freshest(store, job_id: str):
    """``(committed, freshest)`` probed from live adverts: the committed
    base step (or None) and the freshest recoverable delta step past it
    (or None).  Cheap — manifests only, no shard bytes — so restoring
    processes can allgather-agree on one target before fetching."""
    committed = advert.read_committed_step(store, job_id)
    if committed is None:
        return None, None
    listings: dict[str, dict] = {}
    from edl_tpu.rpc.client import RpcChannelPool
    for pod, ep in advert.list_adverts(store, job_id).items():
        try:
            with RpcChannelPool(ep) as pool:
                listings[pod] = pool.call("cache_delta_manifest")
        except Exception as e:  # noqa: BLE001 — dead/old peers: no chains
            logger.debug("delta probe: %s unreachable (%s)", pod[:8], e)
            continue
    plan = plan_freshest(committed, listings)
    return committed, (None if plan is None else int(plan["step"]))


class DeltaReplicator:
    """Trainer-side delta producer (modeled on StateCacheTee).

    The train loop calls :meth:`want`/:meth:`stage` in the hooks phase
    — the host snapshot is the only synchronous cost (the next step
    donates the buffers, the same constraint the tee works under; the
    CRC diff, chunked push and commit all run on the worker thread) —
    and :meth:`rebase` right after every checkpoint save, which
    re-anchors the chain on the new base (one extra D2H per save, at
    checkpoint cadence).  Push targets are the pod's own cache service
    (loopback restores) and its ring replica (failover).  A target
    that rejects or misses a sealed record has a gap and is skipped
    until the next rebase heals it; if NO target seals the record the
    producer keeps its diff baseline, so the next record carries the
    accumulated changes under the same seq — transient push failures
    self-heal without tearing the chain."""

    def __init__(self, store, job_id: str, pod_id: str,
                 src: str | None = None, every: int | None = None,
                 max_chain: int | None = None):
        self._store = store
        self._job_id = job_id
        self._pod_id = pod_id
        self._src = src
        self._every = constants.DELTA_EVERY if every is None else int(every)
        self._max_chain = (constants.DELTA_MAX_CHAIN if max_chain is None
                           else int(max_chain))
        self._base: int | None = None
        self._sealed_step: int | None = None
        self._staged = 0
        self._q: queue.Queue = queue.Queue()
        self._pools: dict[str, tuple[str, object]] = {}
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="memstate-delta")
        self._worker.start()

    # -- producer side (train loop; must stay cheap) ------------------------
    def want(self, step: int) -> bool:
        """Is ``step`` a delta-staging step?  Cheap pure gate so the
        caller can skip the snapshot entirely.  Deterministic across
        processes by construction — it depends only on the cadence knob,
        the base (set at collective save steps) and the staged count —
        because the caller runs a collective span sync before staging
        and every process must take the same branch."""
        return (self._every > 0 and self._base is not None
                and self._staged < self._max_chain
                and int(step) > self._base
                and int(step) % self._every == 0)

    def stage(self, step: int, state, meta=None) -> None:
        """Host-snapshot ``state`` and queue the diff-and-push."""
        import jax
        if self._src is None:
            self._src = str(jax.process_index())
        shard_list, manifest = shards.snapshot(state)
        meta_json = None
        if meta is not None and jax.process_index() == 0:
            meta_json = meta.to_json().encode()
        self._staged += 1
        self._q.put(("push", int(step), shard_list, manifest, meta_json,
                     int(jax.process_count()), time.monotonic()))
        if self._sealed_step is not None:
            _LAG_STEPS.set(int(step) - self._sealed_step)

    def rebase(self, step: int, state) -> None:
        """A checkpoint save just landed at ``step``: snapshot the new
        base's CRCs and start a fresh chain anchored on it."""
        import jax
        if self._src is None:
            self._src = str(jax.process_index())
        shard_list, manifest = shards.snapshot(state)
        self._base = int(step)
        self._staged = 0
        self._q.put(("rebase", int(step), shard_list, manifest))

    def flush(self, timeout: float = 10.0) -> bool:
        """Wait (bounded) for everything queued so far to be processed
        — tests and the failover smoke, never the step path."""
        done = threading.Event()
        self._q.put(("flush", done))
        return done.wait(timeout)

    def close(self, wait: bool = True) -> None:
        """Stop the worker (it closes its own pools on the way out).
        ``wait=False`` just signals — the live-reshard path must not
        block a world re-formation on an RPC to a possibly-dead peer."""
        self._q.put(None)
        if wait:
            self._worker.join(timeout=30.0)

    # -- worker side ---------------------------------------------------------
    def _run(self) -> None:
        base: int | None = None
        seq = 0
        prev = ""
        ref: dict[str, int] = {}     # key -> crc as of the last sealed record
        broken: set[str] = set()     # targets with a gap, until rebase
        while True:
            op = self._q.get()
            if op is None:
                for _ep, pool in self._pools.values():
                    try:
                        pool.close()
                    except Exception as e:  # noqa: BLE001 — exiting anyway
                        logger.debug("delta pool close failed: %s", e)
                self._pools.clear()
                return
            try:
                if op[0] == "rebase":
                    _, step, shard_list, manifest = op
                    shards.finish_manifest(shard_list, manifest)
                    ref = {k: int(e["crc"]) for k, e in manifest.items()}
                    base, seq, prev = step, 0, anchor_hash(step)
                    broken.clear()
                    self._sealed_step = step
                    _CHAIN_LEN.set(0)
                    _LAG_STEPS.set(0)
                elif op[0] == "push":
                    _, step, shard_list, manifest, meta_json, nproc, t0 = op
                    if base is None or step <= base:
                        continue
                    if seq >= self._max_chain > 0:
                        _RECORDS.labels(result="capped").inc()
                        continue
                    blobs = shards.finish_manifest(shard_list, manifest)
                    changed = {k: b for k, b in blobs.items()
                               if int(manifest[k]["crc"]) != ref.get(k)}
                    rec_manifest = {k: dict(manifest[k]) for k in changed}
                    nseq = seq + 1
                    ch = chain_hash(prev, step, nseq, rec_manifest)
                    if self._push_record(base, step, nseq, prev, ch, changed,
                                         rec_manifest, meta_json, nproc,
                                         broken):
                        seq, prev = nseq, ch
                        for k, e in rec_manifest.items():
                            ref[k] = int(e["crc"])
                        self._sealed_step = step
                        _BYTES.inc(sum(len(b) for b in changed.values()))
                        _CHAIN_LEN.set(seq)
                        _LAG.observe(time.monotonic() - t0)
                        _RECORDS.labels(result="sealed").inc()
                        if seq == 1:
                            # once per checkpoint interval: the plane is
                            # alive on this base
                            logger.info(
                                "delta: chain on base step %d opened at "
                                "step %d (%d changed shards, %d bytes)",
                                base, step, len(changed),
                                sum(len(b) for b in changed.values()))
                    else:
                        _RECORDS.labels(result="failed").inc()
                elif op[0] == "flush":
                    op[1].set()
            except Exception:  # noqa: BLE001 — deltas are best-effort
                logger.exception("delta replicator op %s failed; the chain "
                                 "resumes at the next record", op[0])
                _RECORDS.labels(result="failed").inc()

    def _push_record(self, base, step, nseq, prev, ch, changed, rec_manifest,
                     meta_json, nproc, broken) -> bool:
        from edl_tpu.memstate.service import push_shards_parallel
        adverts = advert.list_adverts(self._store, self._job_id)
        targets = [t for t in dict.fromkeys(
            [self._pod_id, placement.replica_for(self._pod_id,
                                                 list(adverts))])
            if t is not None and t in adverts]
        sealed, errored = False, []
        wire = wire_owner(self._pod_id, self._src or "0", nseq)
        for target in targets:
            if target in broken:
                continue
            try:
                pool = self._pool(target, adverts[target])
                push_shards_parallel(pool, changed, owner=wire, step=step)
                resp = pool.call(
                    "cache_delta_commit", owner=self._pod_id, src=self._src,
                    base_step=base, step=step, seq=nseq, prev_hash=prev,
                    chain_hash=ch, manifest=rec_manifest, nproc=nproc,
                    meta=meta_json) or {}
                if resp.get("ok"):
                    sealed = True
                else:
                    # the target refused (stale base, linkage, cap):
                    # its copy has a gap until the next rebase re-anchors
                    broken.add(target)
                    _BREAKS.labels(
                        reason=str(resp.get("reason") or "reject")).inc()
                    logger.warning("delta: %s rejected seq %d (%s)",
                                   target[:8], nseq, resp.get("reason"))
            except Exception as e:  # noqa: BLE001 — per-target best effort
                errored.append(target)
                self._drop_pool(target)
                logger.warning("delta: push of seq %d to %s failed (%s)",
                               nseq, target[:8], e)
        if sealed:
            # a holder that missed a record OTHERS sealed now has a gap
            for target in errored:
                broken.add(target)
                _BREAKS.labels(reason="push").inc()
        # not sealed anywhere: baseline unchanged, the next record
        # retries the same seq with the accumulated diff — no gap
        return sealed

    def _pool(self, target: str, endpoint):
        cached = self._pools.get(target)
        if cached is not None and cached[0] == endpoint:
            return cached[1]
        self._drop_pool(target)
        from edl_tpu.rpc.client import RpcChannelPool
        pool = RpcChannelPool(endpoint)
        self._pools[target] = (endpoint, pool)
        return pool

    def _drop_pool(self, target: str) -> None:
        cached = self._pools.pop(target, None)
        if cached is not None:
            try:
                cached[1].close()
            except Exception as e:  # noqa: BLE001 — pool being replaced
                logger.debug("delta pool close failed: %s", e)
