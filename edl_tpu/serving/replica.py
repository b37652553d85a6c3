"""ReplicaServer: a ContinuousBatcher behind the EDL1 RPC wire, leased
into the gateway fleet.

One replica = one engine + one RPC server + one TTL-leased advert
(``gateway/fleet.py``) that carries live load stats.  The wire protocol
is poll-based so a gateway leg detects replica death within one wait
slice and long generations never monopolize a connection:

- ``serve_submit(request_id, prompt, max_new)`` — enqueue (idempotent
  on ``request_id``, so a gateway transport retry is safe);
- ``serve_wait(request_id, timeout)`` — bounded block; ``{"done":
  False}`` or ``{"done": True, "nbytes": N}``;
- ``serve_fetch(request_id, offset, length)`` — chunk reads of the
  finished int32 token buffer (``rpc/chunks.fetch_bytes``), so a
  multi-KB generation streams in bounded frames;
- ``serve_release(request_id)`` — drop the buffer (ack, or a hedge
  loser's cancel; un-acked buffers expire after
  ``EDL_TPU_SERVING_RESULT_TTL``);
- ``serve_stats`` / ``serve_drain`` — introspection + graceful removal.

**Elastic integration**: ``drain()`` is the preempt path — stop
admission (new submits get :class:`EdlUnavailableError`, and the advert
flips ``draining`` so gateways stop routing here), let queued +
in-flight requests finish, then release the lease.  The RPC server
stays up until ``close()`` so gateways can still fetch finished
buffers.  The engine's own stats are republished as ``edl_serving_*``
gauges on every advert refresh, so a replica's /metrics endpoint covers
the engine, not just the RPC plumbing.
"""

from __future__ import annotations

import json
import os
import threading
import time
import uuid
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeout

import numpy as np

from edl_tpu.coord.session import CoordSession
from edl_tpu.gateway import fleet
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.serving.engine import ContinuousBatcher
from edl_tpu.rpc.server import RpcServer
from edl_tpu.utils import constants
from edl_tpu.utils.exceptions import (
    EdlCoordError,
    EdlInternalError,
    EdlUnavailableError,
)
from edl_tpu.utils.logger import get_logger
from edl_tpu.utils.network import local_ip

logger = get_logger(__name__)

_FREE_SLOTS = obs_metrics.gauge(
    "edl_serving_free_slots", "Engine decode slots currently free")
_QUEUE_DEPTH = obs_metrics.gauge(
    "edl_serving_queue_depth", "Engine requests queued awaiting a slot")
_PREFILL_STALL = obs_metrics.gauge(
    "edl_serving_prefill_stall_seconds",
    "Cumulative host time dispatching prefills while decode lanes were live")
_TOKENS_PER_S = obs_metrics.gauge(
    "edl_serving_tokens_per_s", "Engine tokens emitted per second (lifetime)")
_ACTIVE_SLOTS = obs_metrics.gauge(
    "edl_serving_active_slots", "Engine decode slots serving a live request")
_REPLICA_REQS = obs_metrics.counter(
    "edl_serving_requests_total",
    "Requests accepted by this replica's RPC surface")
_RELEASED = obs_metrics.counter(
    "edl_serving_releases_total",
    "Result buffers released, by cause", ("cause",))
_KV_BLOCKS_USED = obs_metrics.gauge(
    "edl_serving_kv_blocks_used",
    "Paged-KV pool blocks holding committed chains")
_KV_BLOCKS_FREE = obs_metrics.gauge(
    "edl_serving_kv_blocks_free", "Paged-KV pool blocks on the free list")
_KV_PREFIX_HITS = obs_metrics.gauge(
    "edl_serving_kv_prefix_hits",
    "Admissions that resumed from a committed prefix chain (lifetime)")
_KV_PREFIX_MISSES = obs_metrics.gauge(
    "edl_serving_kv_prefix_misses",
    "Admissions that prefilled from position 0 (lifetime)")
_KV_SKIPPED = obs_metrics.gauge(
    "edl_serving_kv_prefill_tokens_skipped",
    "Prompt tokens whose prefill was skipped via prefix reuse (lifetime)")
_KV_EVICTIONS = obs_metrics.gauge(
    "edl_serving_kv_evictions",
    "Unpinned LRU chains evicted to make room for new commits (lifetime)")
_KV_SESSIONS = obs_metrics.gauge(
    "edl_serving_kv_sessions", "Session chains currently pinned")
_KV_MIGRATED = obs_metrics.counter(
    "edl_serving_kv_migrated_sessions_total",
    "Session KV chains moved across a drain, by direction", ("direction",))
_KV_MIGRATION_SECONDS = obs_metrics.histogram(
    "edl_serving_kv_migration_seconds",
    "Wall time exporting + pushing one session chain on drain")
_PREFILL_CHUNKS = obs_metrics.counter(
    "edl_serving_prefill_chunks_total",
    "Prompt chunks dispatched by chunked prefill")
_SPEC_PROPOSED = obs_metrics.counter(
    "edl_serving_spec_proposed_total",
    "Draft tokens proposed by speculative decoding")
_SPEC_ACCEPTED = obs_metrics.counter(
    "edl_serving_spec_accepted_total",
    "Proposed draft tokens the target's greedy verify pass accepted")
_SPEC_ACCEPT_RATE = obs_metrics.gauge(
    "edl_serving_spec_accept_rate",
    "Lifetime fraction of proposed draft tokens accepted")


def _stamp_resolved(fut: Future) -> None:
    fut.t_resolved = time.monotonic()


def publish_engine_stats(stats: dict, totals: dict | None = None) -> None:
    """Mirror :meth:`ContinuousBatcher.stats` into the metrics registry
    (the replica's /metrics page must cover the engine itself).

    ``totals`` holds the last published value of every stat mirrored as
    a Prometheus COUNTER (the engine reports lifetime totals, counters
    take deltas).  It is caller-owned, per replica — two in-process
    replicas sharing module state would double- or under-count."""
    _FREE_SLOTS.set(stats["slots"] - stats["active_slots"])
    _QUEUE_DEPTH.set(stats["queue_depth"])
    _PREFILL_STALL.set(stats["prefill_stall_s"])
    _TOKENS_PER_S.set(stats["tokens_per_s"])
    _ACTIVE_SLOTS.set(stats["active_slots"])
    if "kv_blocks_used" in stats:
        _KV_BLOCKS_USED.set(stats["kv_blocks_used"])
        _KV_BLOCKS_FREE.set(stats["kv_blocks_free"])
        _KV_PREFIX_HITS.set(stats["kv_prefix_hits"])
        _KV_PREFIX_MISSES.set(stats["kv_prefix_misses"])
        _KV_SKIPPED.set(stats["kv_prefill_tokens_skipped"])
        _KV_EVICTIONS.set(stats["kv_evictions"])
        _KV_SESSIONS.set(stats["kv_sessions"])
    if "spec_accept_rate" in stats:
        _SPEC_ACCEPT_RATE.set(stats["spec_accept_rate"])
    if totals is not None:
        for key, metric in (("prefill_chunks", _PREFILL_CHUNKS),
                            ("spec_proposed", _SPEC_PROPOSED),
                            ("spec_accepted", _SPEC_ACCEPTED)):
            cur = int(stats.get(key, 0))
            delta = cur - totals.get(key, 0)
            if delta > 0:
                metric.inc(delta)
            totals[key] = cur


class ReplicaServer:
    """Own the wire + advert around one engine.  ``store`` is any
    KVStore (MemoryKV in tests, CoordClient in a job)."""

    def __init__(self, store, job_id: str, engine: ContinuousBatcher, *,
                 replica_id: str | None = None, host: str = "0.0.0.0",
                 port: int = 0, ttl: float = constants.ETCD_TTL,
                 advert_period: float = constants.SERVING_ADVERT_PERIOD,
                 result_ttl: float = constants.SERVING_RESULT_TTL,
                 migrate_sessions: bool | None = None):
        self._engine = engine
        self._store = store
        self._job_id = job_id
        self._ttl = ttl
        self._migrate = (bool(constants.KV_MIGRATE)
                         if migrate_sessions is None else migrate_sessions)
        self.replica_id = replica_id or (
            f"{local_ip()}-{os.getpid()}-{uuid.uuid4().hex[:6]}")
        self._lock = threading.Lock()
        self._futures: dict[str, Future] = {}
        # rid -> (buf, t fetched-from, t the future resolved)
        self._results: dict[str, tuple[bytes, float, float]] = {}
        self._result_ttl = result_ttl
        self._draining = False
        self._drained = threading.Event()
        self._metric_totals: dict[str, int] = {}   # counter-mirror state
        self._import_staging: dict[str, dict] = {}   # session -> staging
        self._session_pins: dict[str, object] = {}  # session -> Register
        self._pin_misses: dict[str, int] = {}   # pruner-thread-only state
        self._rpc = RpcServer(host=host, port=port)
        for name in ("serve_submit", "serve_wait", "serve_fetch",
                     "serve_release", "serve_stats", "serve_drain",
                     "serve_kv_import_begin", "serve_kv_import_chunk"):
            self._rpc.register(name, getattr(self, name))
        self._rpc.start()
        self.endpoint = self._rpc.endpoint
        # one shared lease for the advert AND every session pin: an
        # adopting replica must not mint a keepalive thread + lease per
        # migrated session (PR-6 shared-session idiom)
        self._coord_session = CoordSession(
            store, ttl=ttl, name=f"replica:{self.replica_id[:8]}")
        self._register = fleet.advertise(store, job_id, self.replica_id,
                                         self._payload(), ttl=ttl,
                                         session=self._coord_session)
        self._halt = threading.Event()
        self._advert_thread = threading.Thread(
            target=self._refresh_loop, args=(advert_period,), daemon=True,
            name=f"replica-advert:{self.replica_id[:8]}")
        self._advert_thread.start()
        logger.info("replica %s serving on %s", self.replica_id,
                    self.endpoint)

    # -- wire surface --------------------------------------------------------
    def serve_submit(self, request_id: str, prompt, max_new: int,
                     session: str | None = None) -> dict:
        with self._lock:
            if self._draining:
                raise EdlUnavailableError(
                    f"replica {self.replica_id} draining")
            if request_id in self._futures or request_id in self._results:
                return {"ok": True}      # idempotent transport retry
        # session rides as a kwarg only when present, so engines without
        # chain pinning (fakes, pre-paged builds) keep their signature
        kwargs = {} if session is None else {"session": session}
        try:
            fut = self._engine.submit(np.asarray(prompt, np.int32),
                                      int(max_new), **kwargs)
        except RuntimeError as e:
            # engine draining/stopping: replica-level, go elsewhere
            raise EdlUnavailableError(str(e)) from e
        with self._lock:
            self._futures[request_id] = fut
        # the instant the answer exists: where its way out begins
        # (serve_release observes the stage ``deliver`` from here)
        fut.add_done_callback(_stamp_resolved)
        _REPLICA_REQS.inc()
        # runs under the RPC wire's re-established context, so this
        # span carries the GATEWAY's trace_id — the cross-process link
        # `edl-obs-dump --merge` joins on
        obs_trace.emit("serving/submit", request=request_id,
                       replica=self.replica_id)
        return {"ok": True}

    def serve_wait(self, request_id: str, timeout: float = 0.2) -> dict:
        with self._lock:
            buf = self._results.get(request_id)
            fut = self._futures.get(request_id)
        if buf is not None:
            return {"done": True, "nbytes": len(buf[0])}
        if fut is None:
            raise EdlInternalError(f"unknown request {request_id}")
        try:
            toks = fut.result(timeout=min(float(timeout), 30.0))
        except FutureTimeout:
            return {"done": False}
        except RuntimeError as e:
            with self._lock:
                self._futures.pop(request_id, None)
            # "engine stopped mid-generation" etc.: the work is not
            # coming; typed retryable so the gateway replays elsewhere
            raise EdlUnavailableError(str(e)) from e
        except Exception as e:
            with self._lock:
                self._futures.pop(request_id, None)
            raise EdlInternalError(
                f"generation failed: {type(e).__name__}: {e}") from e
        data = np.asarray(toks, np.int32).tobytes()
        now = time.monotonic()
        with self._lock:
            self._futures.pop(request_id, None)
            # a waiter can wake before the future's callbacks have run
            self._results[request_id] = (
                data, now, getattr(fut, "t_resolved", now))
        obs_trace.emit("serving/complete", request=request_id,
                       replica=self.replica_id, nbytes=len(data))
        return {"done": True, "nbytes": len(data)}

    def serve_fetch(self, request_id: str, offset: int, length: int) -> bytes:
        with self._lock:
            buf = self._results.get(request_id)
        if buf is None:
            raise EdlInternalError(f"no result for request {request_id}")
        return buf[0][int(offset):int(offset) + int(length)]

    def serve_release(self, request_id: str) -> dict:
        with self._lock:
            result = self._results.pop(request_id, None)
            fut = self._futures.pop(request_id, None)
        if fut is not None and not fut.done():
            # hedge loser cancelled mid-generation: the engine lane
            # still finishes; discard its output on arrival
            fut.add_done_callback(lambda _f: _RELEASED.labels(
                cause="cancelled").inc())
        elif result is not None:
            _RELEASED.labels(cause="acked").inc()
            # the answer's way out, seen from inside: the serve_wait
            # wake-up and the serve_fetch / serve_release round trips,
            # into the engine's request-stage ledger (a fake engine has
            # none)
            observe = getattr(self._engine, "observe_stage", None)
            if observe is not None:
                observe("deliver", time.monotonic() - result[2])
        return {"ok": True}

    def serve_stats(self) -> dict:
        with self._lock:
            tracked = len(self._futures) + len(self._results)
            draining = self._draining
        return {"replica": self.replica_id, "endpoint": self.endpoint,
                "draining": draining, "tracked_requests": tracked,
                "engine": self._engine.stats()}

    def serve_drain(self, timeout: float | None = None) -> dict:
        """Kick off a graceful drain in the background and return
        immediately (the caller may be the preempting launcher on its
        grace budget)."""
        threading.Thread(target=self.drain, args=(timeout,), daemon=True,
                         name=f"replica-drain:{self.replica_id[:8]}").start()
        return {"ok": True}

    def serve_kv_import_begin(self, session: str, tokens: list,
                              meta: dict, nbytes: int) -> dict:
        """Open a staging buffer for one migrated session chain (pushed
        by a DRAINING peer).  Refused immediately when this engine can't
        adopt it — the exporter then lets the session cold-start."""
        if getattr(self._engine, "import_session", None) is None or \
                not self._engine.stats().get("kv_block"):
            raise EdlUnavailableError(
                f"replica {self.replica_id} has no paged KV cache; "
                "session migration refused")
        with self._lock:
            if self._draining:
                raise EdlUnavailableError(
                    f"replica {self.replica_id} draining; cannot adopt")
            self._import_staging[session] = {
                "tokens": [int(t) for t in tokens], "meta": meta,
                "nbytes": int(nbytes), "buf": bytearray(), "seq": 0,
                "t": time.monotonic()}
        return {"ok": True}

    def serve_kv_import_chunk(self, session: str, seq: int, data,
                              eof: bool) -> dict:
        """Ordered chunk of a chain blob; on ``eof`` the chain lands on
        the engine thread, the session is pinned here, and the gateway's
        re-pin record is published."""
        with self._lock:
            st = self._import_staging.get(session)
            if st is None:
                raise EdlInternalError(
                    f"no kv import in progress for session {session}")
            if int(seq) != st["seq"]:
                del self._import_staging[session]
                raise EdlInternalError(
                    f"kv import chunk {seq} out of order "
                    f"(want {st['seq']})")
            st["seq"] += 1
            st["t"] = time.monotonic()
            st["buf"].extend(data)
            if not eof:
                return {"ok": True}
            del self._import_staging[session]
        if len(st["buf"]) != st["nbytes"]:
            raise EdlInternalError(
                f"kv import for {session}: {len(st['buf'])} of "
                f"{st['nbytes']} bytes at eof")
        try:
            blocks = self._engine.import_session(
                session, st["tokens"], st["meta"], bytes(st["buf"]))
        except (RuntimeError, ValueError, TimeoutError) as e:
            raise EdlUnavailableError(
                f"kv import failed on {self.replica_id}: {e}") from e
        self._pin_session(session)
        _KV_MIGRATED.labels(direction="in").inc()
        obs_trace.emit("serving/kv_import", session=session,
                       replica=self.replica_id, blocks=blocks,
                       nbytes=st["nbytes"])
        return {"ok": True, "blocks": blocks}

    def _pin_session(self, session: str) -> None:
        """Publish (or refresh) the gateway-visible pin record mapping
        this session to this replica."""
        with self._lock:
            old = self._session_pins.pop(session, None)
        if old is not None:
            old.stop()
        handle = fleet.pin_session(self._store, self._job_id, session,
                                   self.replica_id, ttl=self._ttl,
                                   coord_session=self._coord_session)
        with self._lock:
            self._session_pins[session] = handle

    # -- lifecycle -----------------------------------------------------------
    def drain(self, timeout: float | None = None) -> bool:
        """The preempt path: stop admission, advertise ``draining`` so
        gateways route elsewhere, finish queued + in-flight requests,
        then release the lease.  The RPC server stays up (finished
        buffers remain fetchable) until :meth:`close`."""
        with self._lock:
            already = self._draining
            self._draining = True
        if already:
            return self._drained.wait(timeout)
        try:
            self._register.update(json.dumps(self._payload()).encode())
        except Exception as e:  # noqa: BLE001 — advert refresh is best-effort
            logger.debug("draining-advert refresh failed (%s); the lease "
                         "expires the stale advert", e)
        ok = self._engine.drain(timeout)
        if ok and self._migrate:
            try:
                self._migrate_sessions()
            except Exception:  # noqa: BLE001 — migration is best-effort:
                # a failed handoff costs the sessions one cold prefill
                # elsewhere, never the drain itself
                logger.exception("session KV migration failed; sessions "
                                 "will cold-start on their next turn")
        self._stop_session_pins()
        self._halt.set()
        self._register.stop()
        self._drained.set()
        logger.info("replica %s drained (complete=%s)", self.replica_id, ok)
        return ok

    def _stop_session_pins(self) -> None:
        with self._lock:
            pins, self._session_pins = self._session_pins, {}
        for handle in pins.values():
            try:
                handle.stop()
            except Exception as e:  # noqa: BLE001 — teardown
                logger.debug("session pin release failed: %s", e)

    def _migrate_sessions(self) -> None:
        """The drain handoff: export every pinned session chain from the
        (now stopped) engine and push each to an adoptive replica over
        the chunked wire; the adopter pins the session in the coord
        store so the gateway re-routes its next turn there.  Any failure
        is per-session — a refused or interrupted push means that
        session cold-starts, never a stuck drain."""
        from edl_tpu.rpc.client import RpcClient
        from edl_tpu.rpc import chunks

        export = getattr(self._engine, "export_sessions", None)
        if export is None:      # duck-typed pre-paging engine: no chains
            return
        exported = export()
        if not exported:
            return
        # release OUR pin records first so the adopter's re-pin is the
        # only record the gateway can see
        self._stop_session_pins()
        replicas = fleet.list_replicas(self._store, self._job_id)
        # only paged peers can adopt — the advert carries kv_block
        # exactly so capability is known without a probe RPC
        cands = {rid: p for rid, p in replicas.items()
                 if rid != self.replica_id and not p.get("draining")
                 and p.get("kv_block")}
        if not cands:
            logger.warning("no paged adoptive replica for %d session "
                           "chains; they will cold-start", len(exported))
            return
        ranked = sorted(cands, key=lambda r: (
            int(cands[r].get("queue_depth", 0))
            - int(cands[r].get("free_slots", 0)), r))
        moved = 0
        # one connection per candidate for the WHOLE export loop — a
        # drain under a preemption deadline must not pay TCP setup per
        # session when most chains go to the same first-ranked peer
        clients: dict[str, RpcClient] = {}
        try:
            for session, tokens, meta, blob in exported:
                t0 = time.monotonic()
                target = None
                for cand in list(ranked):   # a refusal tries the next peer
                    try:
                        client = clients.get(cand)
                        if client is None:
                            client = clients[cand] = RpcClient(
                                cands[cand]["endpoint"], timeout=10.0)
                        client.call("serve_kv_import_begin",
                                    session=session, tokens=tokens,
                                    meta=meta, nbytes=len(blob))
                        chunks.push_bytes(
                            lambda **kw: client.call(
                                "serve_kv_import_chunk",
                                session=session, **kw),
                            blob)
                        target = cand
                        break
                    except EdlCoordError as e:
                        # transport failure: the peer is dead or hung —
                        # later sessions must not re-pay its timeout
                        client = clients.pop(cand, None)
                        if client is not None:
                            client.close()
                        ranked.remove(cand)
                        logger.warning("session %s migration to %s "
                                       "failed (%s); peer dropped",
                                       session, cand, e)
                    except Exception as e:  # noqa: BLE001 — this peer only
                        # typed server-side refusal (no paging, pool
                        # exhausted, layout mismatch): the connection is
                        # healthy and the peer may still adopt a LATER
                        # (smaller/dedupable) chain — keep both
                        logger.warning("session %s migration to %s "
                                       "refused (%s)", session, cand, e)
                if target is None:
                    logger.warning("session %s found no adopter; it "
                                   "will cold-start", session)
                    continue
                _KV_MIGRATED.labels(direction="out").inc()
                _KV_MIGRATION_SECONDS.observe(time.monotonic() - t0)
                obs_trace.emit("serving/kv_export", session=session,
                               replica=self.replica_id, target=target,
                               nbytes=len(blob))
                moved += 1
        finally:
            for client in clients.values():
                client.close()
        logger.info("replica %s migrated %d/%d session chains on drain",
                    self.replica_id, moved, len(exported))

    def close(self) -> None:
        """Hard teardown: advert gone, engine stopped (in-flight futures
        FAIL — use :meth:`drain` first for graceful removal)."""
        self._halt.set()
        self._advert_thread.join(timeout=5.0)
        self._stop_session_pins()
        self._register.stop()
        self._coord_session.close()
        self._engine.stop()
        self._rpc.stop()

    # -- internals -----------------------------------------------------------
    def _payload(self) -> dict:
        s = self._engine.stats()
        with self._lock:
            draining = self._draining
        payload = {"endpoint": self.endpoint, "slots": s["slots"],
                   "free_slots": s["slots"] - s["active_slots"],
                   "queue_depth": s["queue_depth"],
                   "prefill_stall_s": s["prefill_stall_s"],
                   "tokens_per_s": s["tokens_per_s"],
                   "max_prompt_len": s["max_prompt_len"],
                   "draining": draining, "ts": time.time()}
        if s.get("kv_block"):
            # prefix-hit-aware routing stat: gateways (and operators
            # reading the advert) see how warm this replica's cache
            # runs without scraping its /metrics page
            admits = s["kv_prefix_hits"] + s["kv_prefix_misses"]
            payload["kv_block"] = s["kv_block"]
            payload["kv_blocks_free"] = s["kv_blocks_free"]
            payload["kv_prefix_hit_rate"] = round(
                s["kv_prefix_hits"] / admits, 3) if admits else 0.0
        if s.get("spec_k"):
            payload["spec_k"] = s["spec_k"]
            payload["spec_accept_rate"] = s["spec_accept_rate"]
        return payload

    def _refresh_loop(self, period: float) -> None:
        while not self._halt.wait(period):
            if not self._register.is_stopped:
                try:
                    self._register.update(
                        json.dumps(self._payload()).encode())
                except Exception as e:  # noqa: BLE001 — Register self-heals
                    logger.warning("advert refresh failed: %s", e)
            publish_engine_stats(self._engine.stats(), self._metric_totals)
            self._evict_stale_results()
            self._prune_session_pins()

    def _prune_session_pins(self) -> None:
        """Drop the coord pin of any session whose chain the engine's
        session LRU has since unpinned — the pin would only misroute
        (guaranteed prefix miss) and otherwise accumulates forever on a
        long-lived adopter.  Pins are snapshotted BEFORE the engine
        read (a session adopted concurrently is pinned in the engine
        before its handle lands here, so it can never look dead), and a
        pin is only dropped after TWO consecutive periods absent — a
        session the engine re-pins between our snapshot and the stop
        (its turn finished right then) survives the race; worst case a
        genuinely-racing session costs one cold re-route."""
        with self._lock:
            candidates = list(self._session_pins)
        if not candidates:
            return
        poll = getattr(self._engine, "kv_pinned_sessions", None)
        snap = poll() if poll is not None else None
        if snap is None:        # racy read lost; retry next period
            return
        live = set(snap)
        misses = self._pin_misses
        for s in candidates:
            misses[s] = misses.get(s, 0) + 1 if s not in live else 0
        for s in [s for s in misses if s not in candidates or not misses[s]]:
            del misses[s]
        with self._lock:
            dead = {s: self._session_pins.pop(s) for s in candidates
                    if misses.get(s, 0) >= 2 and s in self._session_pins}
        for session, handle in dead.items():
            misses.pop(session, None)
            try:
                handle.stop()
            except Exception as e:  # noqa: BLE001 — lease lapses it anyway
                logger.debug("pruned pin release for %s failed: %s",
                             session, e)
            logger.info("session %s pin pruned (engine unpinned its "
                        "chain)", session)

    # a migration push abandoned mid-stream (exporter SIGKILLed between
    # chunks) would otherwise park its partial blob forever; one minute
    # is orders of magnitude beyond a live push's inter-chunk gap
    _IMPORT_STAGING_TTL = 60.0

    def _evict_stale_results(self) -> None:
        cutoff = time.monotonic() - self._IMPORT_STAGING_TTL
        with self._lock:
            for session in [s for s, st in self._import_staging.items()
                            if st["t"] < cutoff]:
                del self._import_staging[session]
                logger.warning("kv import for session %s abandoned "
                               "mid-stream; staging dropped", session)
        if not self._result_ttl:
            return
        cutoff = time.monotonic() - self._result_ttl
        with self._lock:
            stale = [rid for rid, res in self._results.items()
                     if res[1] < cutoff]
            for rid in stale:
                del self._results[rid]
        for _ in stale:
            _RELEASED.labels(cause="expired").inc()


def main(argv: list[str] | None = None) -> None:  # pragma: no cover - thin CLI
    """``edl-replica`` / ``python -m edl_tpu.serving.replica``: build a
    TransformerLM engine (seeded init, or a TrainState checkpoint via
    ``--checkpoint_dir``) and lease it into the fleet."""
    import argparse

    import jax
    import jax.numpy as jnp

    from edl_tpu import obs
    from edl_tpu.coord.client import connect
    from edl_tpu.models.transformer import TransformerConfig, TransformerLM
    from edl_tpu.obs import advert as obs_advert
    from edl_tpu.utils.logger import configure

    p = argparse.ArgumentParser("edl_tpu.serving.replica")
    p.add_argument("--coord_endpoints", required=True)
    p.add_argument("--job_id", required=True)
    p.add_argument("--replica_id", default=None)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--checkpoint_dir", default=None)
    p.add_argument("--vocab", type=int, default=53)
    p.add_argument("--layers", type=int, default=1)
    p.add_argument("--embed", type=int, default=32)
    p.add_argument("--heads", type=int, default=2)
    p.add_argument("--mlp", type=int, default=64)
    p.add_argument("--max_len", type=int, default=64)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--steps_per_sync", type=int, default=4)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top_k", type=int, default=0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ttl", type=float, default=constants.ETCD_TTL)
    p.add_argument("--kv_block", type=int, default=constants.KV_BLOCK,
                   help="paged-KV block size in tokens; 0 = contiguous "
                        "slabs, no prefix reuse (EDL_TPU_KV_BLOCK)")
    p.add_argument("--kv_pool_blocks", type=int,
                   default=constants.KV_POOL_BLOCKS,
                   help="paged-KV pool size; 0 = 2x the slot capacity "
                        "(EDL_TPU_KV_POOL_BLOCKS)")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel width; > 1 builds a (dp, tp) "
                        "mesh and shards the engine (incl. the paged KV "
                        "pool) over it")
    p.add_argument("--prefill_chunk", type=int,
                   default=constants.PREFILL_CHUNK,
                   help="chunked-prefill chunk size in tokens; 0 = "
                        "monolithic prefills (EDL_TPU_PREFILL_CHUNK)")
    p.add_argument("--spec_k", type=int, default=constants.SPEC_K,
                   help="speculative-decode draft length; 0 = off "
                        "(EDL_TPU_SPEC_K; greedy sampling only)")
    p.add_argument("--draft_layers", type=int, default=1)
    p.add_argument("--draft_embed", type=int, default=16)
    p.add_argument("--draft_heads", type=int, default=2)
    p.add_argument("--draft_mlp", type=int, default=32)
    p.add_argument("--draft_seed", type=int, default=None,
                   help="seeded-init draft params (default: --seed; "
                        "matching dims + seed = a self-draft, handy for "
                        "parity smokes)")
    args = p.parse_args(argv)
    configure()
    from edl_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()
    logger.info("devices: platform=%s device_kind=%s count=%d",
                dev[0].platform, dev[0].device_kind, len(dev))
    obs.install_from_env("replica")
    # /profile on the replica's metrics endpoint: the gateway-p99-slo
    # alert action captures HERE (jax.profiler on real accelerators;
    # manifest-only on CPU — no step ledger runs in a replica)
    from edl_tpu.obs import profile as obs_profile
    obs_profile.install_route(obs_profile.ProfileCapture("replica"))

    cfg = TransformerConfig(vocab_size=args.vocab, num_layers=args.layers,
                            embed_dim=args.embed, num_heads=args.heads,
                            mlp_dim=args.mlp, max_len=args.max_len,
                            remat=False, dtype=jnp.float32)
    if args.checkpoint_dir:
        import optax

        from edl_tpu.train.checkpoint import CheckpointManager
        from edl_tpu.train.state import TrainState

        model = TransformerLM(cfg)
        shape = jax.eval_shape(
            lambda: model.init(jax.random.key(0),
                               jnp.zeros((1, 4), jnp.int32))["params"])
        abstract = TrainState.create(shape, optax.adamw(1e-3))
        ck = CheckpointManager(args.checkpoint_dir)
        restored = ck.restore(abstract)
        if restored is None:
            raise SystemExit(f"no checkpoint under {args.checkpoint_dir}")
        params = restored[0].params
        del restored        # the optimizer moments leave the device
        ck.close()
    else:
        params = TransformerLM(cfg).init(
            jax.random.key(args.seed), jnp.zeros((1, 4), jnp.int32))["params"]

    mesh = None
    if args.tp > 1:
        from edl_tpu.parallel import MeshSpec, build_mesh
        mesh = build_mesh(MeshSpec(dp=-1, tp=args.tp))
    draft_cfg = draft_params = None
    if args.spec_k > 0:
        draft_cfg = TransformerConfig(
            vocab_size=args.vocab, num_layers=args.draft_layers,
            embed_dim=args.draft_embed, num_heads=args.draft_heads,
            mlp_dim=args.draft_mlp, max_len=args.max_len,
            remat=False, dtype=jnp.float32)
        dseed = args.seed if args.draft_seed is None else args.draft_seed
        draft_params = TransformerLM(draft_cfg).init(
            jax.random.key(dseed), jnp.zeros((1, 4), jnp.int32))["params"]
    engine = ContinuousBatcher(cfg, params, slots=args.slots,
                               temperature=args.temperature,
                               top_k=args.top_k,
                               steps_per_sync=args.steps_per_sync,
                               kv_block=args.kv_block,
                               kv_pool_blocks=args.kv_pool_blocks,
                               prefix_reuse=bool(constants.KV_REUSE),
                               mesh=mesh,
                               prefill_chunk=args.prefill_chunk,
                               spec_k=args.spec_k, draft_cfg=draft_cfg,
                               draft_params=draft_params)
    store = connect(args.coord_endpoints)
    # TTL-leased advert so edl-obs-agg can discover this /metrics page
    obs_advert.advertise_installed(store, args.job_id, "replica")
    server = ReplicaServer(store, args.job_id, engine,
                           replica_id=args.replica_id, host=args.host,
                           port=args.port, ttl=args.ttl)
    print(f"[edl-replica] {server.replica_id} serving on {server.endpoint}",
          flush=True)

    import signal
    done = threading.Event()

    def _sigterm(_sig, _frm):
        # preemption: drain gracefully, then exit (SIGKILL is the hard
        # path the gateway's failover covers)
        threading.Thread(target=lambda: (server.drain(), done.set()),
                         daemon=True).start()

    signal.signal(signal.SIGTERM, _sigterm)
    try:
        done.wait()
        server.close()
    except KeyboardInterrupt:
        server.close()


if __name__ == "__main__":  # pragma: no cover
    main()
