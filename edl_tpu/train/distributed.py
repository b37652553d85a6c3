"""Multi-host bootstrap: ``jax.distributed`` from the launcher env ABI.

The reference launcher exported ``PADDLE_TRAINER_ID/_ENDPOINTS/
_TRAINERS_NUM`` and Fleet's RoleMaker read them
(train_with_fleet.py:376-377); NCCL bootstrapped its uniqueId over
sockets (train_process.py:38-41).  Here the launcher exports
``EDL_TPU_TRAINER_*`` (edl_tpu/cluster/env.py) and this module turns
them into ``jax.distributed.initialize(coordinator, num_processes,
process_id)`` — after which ``jax.devices()`` is the global device set
and a Mesh over it spans the whole job.

Stop-resume resizes never reshape a live world: the launcher restarts
the trainer processes and this runs again with the new env.  The
DELTA-RESIZE path (EDL_TPU_RESIZE_DELTA=1, ISSUE 12) does reshape it:
:func:`initialize_from_env` then forms a *resizable* world — the jax
coordination client/service built by hand so the world can be LEAKED
(``shutdown_on_destruction=False``; this jaxlib's default client
LOG(FATAL)s the process whenever a shutdown barrier fails or an error
broadcast reaches its poll thread, so a world that lost a member can
never be shut down, only abandoned) — and :func:`reform_world` re-forms
a new one in the SAME process: drop every device array, clear backends,
leak the old client+service, and rendezvous on a fresh coordinator port
published through the coordination store (cluster/resize.py
``worldsvc/<stage>``), so nobody ever connects to a stale service.
Heartbeat windows are set effectively infinite: death detection belongs
to the EDL control plane (gloo collectives fail instantly; the launcher
watches membership), and the jax service noticing a dead task would
broadcast an unoverridable process-terminating error to every survivor.
"""

from __future__ import annotations

import os

import jax

from edl_tpu.cluster.env import TrainerEnv, tpu_visibility_vars
from edl_tpu.utils.compile_cache import enable_compile_cache
from edl_tpu.utils.logger import get_logger

logger = get_logger(__name__)

_initialized = False
_resizable = False      # current world formed via the resizable path
_leaked: list = []      # [(client, service)] — kept alive forever (see above)
_exit_code = [0]
_guard_installed = False

# jaxlib 0.9 folded (interval, max-missing) into ONE heartbeat_timeout
# (seconds a silent task may stay silent before the service declares it
# dead).  The old pair meant "never" (600 s x 1e6 misses); this keeps
# the same product, ~19 years, which still fits an int32.  Clients are
# also ``recoverable``: the service then treats a member that vanished
# as restartable instead of broadcasting a process-terminating error to
# every survivor.  Death detection is the launcher's, never jax's.
_HB_TIMEOUT_S = 600 * 1_000_000


def _enable_cpu_collectives() -> None:
    """Multi-process worlds on the CPU platform (integration tests, the
    virtual mesh) need an explicit cross-process collectives backend:
    without one, every collective dies with "Multiprocess computations
    aren't implemented on the CPU backend"."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def _install_exit_guard() -> None:
    """Once a world has been leaked, normal interpreter teardown is no
    longer safe: destroying a leaked service closes its port while
    leaked poll threads (unkillable from Python) still reference it,
    and the resulting error broadcast LOG(FATAL)s the process AFTER
    main() finished — turning a clean exit into an abort.  So from the
    first leak on, the process exits via ``os._exit`` from an atexit
    hook (the same contract the preemption path already uses), with a
    ``sys.excepthook`` wrapper preserving the crashed-exit code."""
    global _guard_installed
    if _guard_installed:
        return
    _guard_installed = True
    import atexit
    import logging
    import sys

    prev_hook = sys.excepthook

    def hook(tp, val, tb):
        _exit_code[0] = 1
        prev_hook(tp, val, tb)

    sys.excepthook = hook

    def bail():
        try:
            for h in logging.getLogger().handlers:
                try:
                    h.flush()
                # edl-lint: disable=wire-error — last-gasp flush before
                # os._exit; logging about a failed log flush cannot work
                except Exception:  # noqa: BLE001
                    pass
            sys.stdout.flush()
            sys.stderr.flush()
        finally:
            os._exit(_exit_code[0])

    atexit.register(bail)


def leak_world() -> None:
    """Abandon the current collective world without shutting it down.

    Order matters and every step is load-bearing: live arrays pin the
    backend, the backend pins the distributed client, and the client's
    error-poll thread turns any service-side close into a process
    abort.  The caller must have dropped every device array reference
    first; this clears the backends (releasing the client ref), then
    stashes the client+service in a never-collected list — their idle
    threads cost a few KB; shutting them down would fatal us."""
    global _initialized, _resizable
    import gc

    gc.collect()
    # force-delete every live array: a single stray reference (an
    # exception chain's frame, a prefetch future, user code) would keep
    # the old backend — and its OPEN GLOO SOCKETS — alive, leaving
    # peers blocked in their collectives on US instead of unwinding.
    # Anything still referencing these arrays is garbage by contract
    # (the caller moved everything it needs to host memory).  Guarded
    # on an ALREADY-initialized backend: jax.live_arrays() would
    # otherwise create one, which fails mid-teardown (gloo configured,
    # no distributed client).
    import weakref

    from jax._src import xla_bridge as _xb
    probe = None
    if _xb._backends:
        try:
            probe = weakref.ref(next(iter(_xb._backends.values())))
        except TypeError:
            probe = None
        for arr in jax.live_arrays():
            try:
                arr.delete()
            # edl-lint: disable=wire-error — best-effort sweep; an
            # array mid-donation can legitimately refuse deletion
            except Exception:  # noqa: BLE001
                pass
    # the FULL teardown (jax._src.api.clear_backends), not the minimal
    # jax.extend.backend one: the lru-cached local_devices /
    # process_count tuples and the primitive-callable cache all hold
    # Device objects, each pinning the old client — and a pinned client
    # keeps its gloo sockets open under blocked peers
    from jax._src.api import clear_backends as _full_clear
    _full_clear()
    jax.clear_caches()
    # a pinner no cache sweep covers (found by walking gc referrers
    # of a leaked client): the Mesh-instance memo dict
    from jax._src import mesh as _jmesh
    _jmesh._mesh_object_dict.clear()
    # plain functools.lru_cache's inside jax (sharding/layout memos)
    # are registered with NO clearing hook and their keys hold
    # NamedSharding -> Mesh -> Device -> client chains.  Sweep them
    # all: caches are semantically transparent, and this runs once per
    # resize, not on any hot path
    import functools
    for obj in gc.get_objects():
        if isinstance(obj, functools._lru_cache_wrapper):
            try:
                if getattr(getattr(obj, "__wrapped__", None), "__module__",
                           "").startswith("jax"):
                    obj.cache_clear()
            # edl-lint: disable=wire-error — best-effort cache sweep
            except Exception:  # noqa: BLE001
                continue
    gc.collect()
    if probe is not None and probe() is not None:
        # the old runtime survived the teardown: its open gloo sockets
        # can keep PEERS blocked in their collectives.  Name the
        # holder CHAINS — this is the diagnostic that localizes a leak
        import threading

        def name(o):
            t = type(o).__name__
            if t == "frame":
                c = o.f_code
                return f"frame[{c.co_filename.rsplit('/', 1)[-1]}:" \
                       f"{c.co_name}:{o.f_lineno}]"
            return f"{t}:{repr(o)[:48]}"

        chains = []
        for r1 in gc.get_referrers(probe())[:6]:
            for r2 in gc.get_referrers(r1)[:5]:
                if type(r2).__name__ == "list":
                    continue
                for r3 in gc.get_referrers(r2)[:4]:
                    if type(r3).__name__ == "list":
                        continue
                    chains.append(
                        f"{name(r1)} <- {name(r2)} <- {name(r3)}")
        threads = [t.name for t in threading.enumerate()]
        logger.warning("old backend still referenced after teardown; "
                       "peers blocked on our sockets may stall until "
                       "this process exits.  threads=%s\n  %s",
                       threads, "\n  ".join(sorted(set(chains))[:16]))
    from jax._src import distributed as _jdist
    gs = _jdist.global_state
    if gs.client is not None or gs.service is not None:
        _leaked.append((gs.client, gs.service, gs.preemption_sync_manager))
        _install_exit_guard()
    gs.client = None
    gs.service = None
    gs.preemption_sync_manager = None
    gs.coordinator_address = None
    gs.process_id = 0
    gs.num_processes = 1
    _initialized = False
    _resizable = False


def host_world_service(store, job_id: str, stage: str, world: int,
                       host: str) -> object:
    """Bind a fresh jax coordination service for ``stage``'s world and
    publish its endpoint as ``worldsvc/<stage>`` — run by the LEADER
    POD'S LAUNCHER, never a trainer: the launcher outlives every
    trainer exit (the same lifetime split the memstate cache uses), so
    the rendezvous service can't die under peers whose error-poll
    threads would terminate their processes.  Returns the service
    handle; the caller keeps it referenced forever (shutting a service
    down while any client's poll is pending aborts that client)."""
    from jaxlib import _jax

    from edl_tpu.cluster import resize as resize_rec
    from edl_tpu.utils.network import find_free_port

    port = find_free_port()
    service = _jax.get_distributed_runtime_service(
        f"[::]:{port}", world, heartbeat_timeout=_HB_TIMEOUT_S)
    endpoint = f"{host or '127.0.0.1'}:{port}"
    resize_rec.publish_world_service(store, job_id, stage, endpoint, world)
    logger.info("hosting world service %s for stage %s (world=%d)",
                endpoint, stage[:8], world)
    return service


def _form_resizable_world(tenv: TrainerEnv, store, timeout: float,
                          min_ts: float = 0.0) -> None:
    """Store-gated formation of a resizable world for ``tenv``'s stage:
    every trainer (rank 0 included) waits for the launcher-hosted
    ``worldsvc/<stage>`` record and connects as a CLIENT.  Fresh port +
    publish-after-bind means no process can ever rendezvous with a
    stale previous-generation service.  ``min_ts`` guards same-stage
    re-formations (a hang restart keeps the stage, so the PREVIOUS
    formation's record may still exist): a respawned trainer refuses
    any record older than its own spawn (minus NTP slack) and polls
    until the leader's launcher republishes."""
    global _initialized, _resizable
    import time

    from jax._src import distributed as _jdist
    from jaxlib import _jax

    from edl_tpu.cluster import resize as resize_rec

    gs = _jdist.global_state
    deadline = time.monotonic() + timeout
    endpoint = None
    while time.monotonic() < deadline:
        rec = resize_rec.read_world_service(store, tenv.job_id,
                                            tenv.cluster_stage)
        if (rec is not None and rec.get("world") == tenv.world_size
                and float(rec.get("ts", 0.0)) >= min_ts):
            endpoint = rec["endpoint"]
            break
        time.sleep(0.1)
    if endpoint is None:
        raise RuntimeError(
            f"no world-service record for stage "
            f"{tenv.cluster_stage[:8]} within {timeout:.0f}s")
    # the connect blocks until every member joins; its expiry is a
    # process-terminating LOG(FATAL) in this jaxlib, so it gets MORE
    # budget than the launcher's reshard-done deadline — a world that
    # can't form is reaped by the launcher's clean SIGTERM fallback,
    # never by an abort
    client = _jax.get_distributed_runtime_client(
        endpoint, tenv.global_rank,
        init_timeout=int(timeout + 30),
        heartbeat_timeout=_HB_TIMEOUT_S,
        shutdown_on_destruction=False, use_compression=True,
        recoverable=True)
    logger.info("connecting to resizable world %s as rank %d/%d",
                endpoint, tenv.global_rank, tenv.world_size)
    client.connect()
    gs.client = client
    gs.process_id = tenv.global_rank
    gs.num_processes = tenv.world_size
    gs.coordinator_address = endpoint
    # orbax's save path gates on the preemption sync manager whenever
    # process_count > 1; it must exist for every formed world
    gs.preemption_sync_manager = _jax.create_preemption_sync_manager()
    gs.preemption_sync_manager.initialize(client)
    _initialized = True
    _resizable = True


def _delta_enabled(tenv: TrainerEnv) -> bool:
    """The resizable path needs the store-gated rendezvous, so it only
    engages under the launcher (stage + coord endpoints present)."""
    from edl_tpu.utils import constants
    return bool(constants.RESIZE_DELTA and tenv.cluster_stage
                and tenv.coord_endpoints)


def initialize_from_env(tenv: TrainerEnv | None = None) -> TrainerEnv:
    """Idempotently bootstrap the multi-process JAX runtime.  Single-host
    (world_size <= 1) is a no-op so the same trainer script runs
    standalone, under tests, and under the elastic launcher.

    After initialize, verifies the world actually formed
    (``jax.process_count() == world_size``) — a half-formed world must
    fail loudly here, not corrupt shared checkpoints later."""
    global _initialized
    tenv = tenv or TrainerEnv()
    enable_compile_cache()
    if tenv.world_size > 1 and not _initialized:
        coordinator = tenv.coordinator or (
            tenv.trainer_endpoints[0] if tenv.trainer_endpoints else "")
        if not coordinator:
            raise RuntimeError(
                "world_size > 1 but no coordinator address: set "
                "EDL_TPU_COORDINATOR or EDL_TPU_TRAINER_ENDPOINTS")
        if jax.config.jax_platforms == "cpu" or \
                os.environ.get("JAX_PLATFORMS") == "cpu":
            _enable_cpu_collectives()
        timeout = int(os.environ.get("EDL_TPU_DIST_INIT_TIMEOUT", "120"))
        retries = max(1, int(os.environ.get("EDL_TPU_DIST_INIT_RETRIES", "3")))
        if _delta_enabled(tenv):
            # resizable formation: reform_world can later reshape this
            # world in place.  The store client is scoped to formation.
            # EDL_TPU_SPAWN_TS (stamped by the spawning launcher, same
            # host = same clock) bounds how old an acceptable worldsvc
            # record may be; 30 s covers cross-host NTP slack on the
            # leader's republish while still rejecting any previous
            # formation's record (hang detection alone takes >= 120 s)
            min_ts = float(os.environ.get("EDL_TPU_SPAWN_TS", 0.0)) - 30.0
            store = None
            try:
                from edl_tpu.coord.client import connect
                store = connect(tenv.coord_endpoints)
                _form_resizable_world(tenv, store, float(timeout),
                                      min_ts=min_ts)
            finally:
                if store is not None:
                    store.close()
            formed = jax.process_count()
            if formed != tenv.world_size:
                raise RuntimeError(
                    f"resizable world did not form: process_count()="
                    f"{formed}, expected {tenv.world_size}")
            return tenv
        logger.info("jax.distributed.initialize(coordinator=%s, n=%d, rank=%d)",
                    coordinator, tenv.world_size, tenv.global_rank)
        for attempt in range(1, retries + 1):
            try:
                jax.distributed.initialize(
                    coordinator_address=coordinator,
                    num_processes=tenv.world_size,
                    process_id=tenv.global_rank,
                    initialization_timeout=timeout)
                break
            except Exception as e:  # noqa: BLE001 — rendezvous is racy
                # under CPU starvation the Gloo/coordinator rendezvous
                # can time out even though every peer is alive (a real
                # loaded-cluster failure mode, observed when multiple
                # suites compete for one core): retry with backoff
                # before declaring the world unformable
                if attempt == retries:
                    raise
                logger.warning(
                    "distributed init failed (attempt %d/%d): %s — "
                    "retrying", attempt, retries, e)
                try:
                    jax.distributed.shutdown()
                except Exception as down_err:  # noqa: BLE001 — partial init
                    logger.debug("shutdown of partial distributed init "
                                 "failed: %s", down_err)
                import time
                time.sleep(2.0 * attempt)
        _initialized = True
        formed = jax.process_count()
        if formed != tenv.world_size:
            raise RuntimeError(
                f"jax.distributed world did not form: process_count()="
                f"{formed}, expected {tenv.world_size} (coordinator "
                f"{coordinator}; platform "
                f"{jax.devices()[0].platform if jax.devices() else '?'})")
    return tenv


def reform_world(tenv: TrainerEnv, store, cluster) -> TrainerEnv:
    """Re-form the collective world IN THIS PROCESS against ``cluster``
    (the new membership record): leak the old world, update ``tenv``
    in place (so every closure holding it sees the new topology) plus
    the process env (so ``TrainerEnv()`` re-reads agree), and
    rendezvous on the new stage's fresh world service.  The caller
    must have dropped every device-array reference first
    (:func:`leak_world`'s contract).

    Raises on any failure — the caller's fallback is exiting nonzero,
    which the launcher turns into a stop-resume respawn."""
    from edl_tpu.utils import constants

    me = cluster.get_pod(tenv.pod_id)
    if me is None:
        raise RuntimeError(
            f"pod {tenv.pod_id[:8]} is not in stage "
            f"{cluster.stage[:8]}; cannot reshard into it")
    if tenv.rank_in_pod >= len(me.trainers):
        raise RuntimeError(
            f"rank_in_pod {tenv.rank_in_pod} exceeds the new pod's "
            f"{len(me.trainers)} trainers")
    leak_world()
    trainer = me.trainers[tenv.rank_in_pod]
    endpoints = cluster.get_trainers_endpoints()
    tenv.global_rank = trainer.global_rank
    tenv.world_size = cluster.world_size
    tenv.trainer_endpoints = list(endpoints)
    tenv.coordinator = endpoints[0] if endpoints else ""
    tenv.pod_rank = me.rank
    tenv.cluster_stage = cluster.stage
    os.environ.update({
        "EDL_TPU_TRAINER_ID": str(tenv.global_rank),
        "EDL_TPU_TRAINERS_NUM": str(tenv.world_size),
        "EDL_TPU_TRAINER_ENDPOINTS": ",".join(endpoints),
        "EDL_TPU_COORDINATOR": tenv.coordinator,
        "EDL_TPU_POD_RANK": str(tenv.pod_rank),
        "EDL_TPU_CLUSTER_STAGE": tenv.cluster_stage,
        # the next backend this process creates must see the NEW
        # world's process layout (no-op without --devices)
        **tpu_visibility_vars(trainer, cluster),
    })
    if tenv.world_size > 1:
        _form_resizable_world(tenv, store,
                              constants.RESIZE_RESHARD_TIMEOUT)
    formed = jax.process_count()
    if formed != tenv.world_size:
        raise RuntimeError(
            f"re-formed world has process_count()={formed}, expected "
            f"{tenv.world_size} (stage {cluster.stage[:8]})")
    logger.info("world re-formed in place: rank %d/%d, stage %s",
                tenv.global_rank, tenv.world_size, cluster.stage[:8])
    return tenv


def connect_store(tenv: TrainerEnv):
    """Coordination-store client for a trainer, or None when running
    standalone (no launcher env / store unreachable) — the common
    trainer-side boilerplate shared by the examples."""
    if not (tenv.coord_endpoints and tenv.pod_id):
        return None
    try:
        from edl_tpu.coord.client import connect
        return connect(tenv.coord_endpoints)
    except Exception:  # noqa: BLE001 — standalone / store gone
        logger.warning("coordination store unreachable; running standalone")
        return None


def shutdown() -> None:
    global _initialized
    if _initialized:
        if _resizable:
            # a resizable world is never shut down (the barrier fatals
            # if any member is gone) — it is abandoned
            leak_world()
        else:
            jax.distributed.shutdown()
            _initialized = False


def is_coordinator(tenv: TrainerEnv | None = None) -> bool:
    tenv = tenv or TrainerEnv()
    return tenv.global_rank == 0
