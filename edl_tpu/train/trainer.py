"""ElasticTrainer: the jitted train loop with stop-resume elasticity.

The reference's training loop lived in user code
(train_with_fleet.py:491-570): epoch loop from ``train_status.next()``,
``train_exe.run`` per step, rank-0 checkpoint per epoch, train-status
records in etcd.  ElasticTrainer packages that contract TPU-natively:

- one jitted, donated train step over a Mesh (gradient reduction is
  XLA collectives implied by shardings — no Fleet graph rewrite);
- epoch accounting + data checkpoint in a :class:`State` sidecar saved
  with the Orbax checkpoint;
- resume = restore latest checkpoint, continue from ``state.next_epoch``
  (train_with_fleet.py:491), with :class:`AdjustRegistry` callbacks on
  world-size change (LR rescale — reference state.py:142);
- train-status reporting (RUNNING / NEARTHEEND) to the coordination
  store so the cluster generator won't scale near job end
  (cluster_generator.py:200-215).

Mid-epoch saves (``save_every_steps``, SIGTERM preemption) re-enter
the in-progress epoch on resume.  Exactly-once record delivery across
that re-entry requires a SPAN-AWARE reader (the data service /
ElasticInput: consumed spans ride the checkpoint and are skipped);
a plain generator ``data_fn`` re-yields the epoch from its start —
at-least-once, the reference's per-epoch granularity.  Epoch-boundary
checkpoints are always exact for both.
"""

from __future__ import annotations

import os
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from edl_tpu.cluster.env import TrainerEnv
from edl_tpu.cluster.state import AdjustRegistry, DataCheckpoint, State
from edl_tpu.utils.constants import DATA_SPANS_KEY as _SPANS_KEY
from edl_tpu.cluster.train_status import TrainStatus, save_train_status
from edl_tpu.parallel.mesh import MeshSpec, batch_divisor, build_mesh
from edl_tpu.parallel.sharding import (
    ShardingRules, logical_sharding, shard_host_batch,
)
from edl_tpu.obs import flops as obs_flops
from edl_tpu.obs import ledger as obs_ledger
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import profile as obs_profile
from edl_tpu.obs import trace as obs_trace
from edl_tpu.train.checkpoint import CheckpointManager
from edl_tpu.train.state import TrainState, abstract_like
from edl_tpu.utils.logger import get_logger

logger = get_logger(__name__)
_BUILDS = obs_ledger.PROGRAM_BUILDS

# step latency is the wall time between completed-step observations:
# steps dispatch asynchronously, but with a bounded dispatch queue the
# steady-state loop rate equals the device step rate (see
# _observe_step_time), so the histogram converges on true step time
# without forcing a device sync per step
_STEP_SECONDS = obs_metrics.histogram(
    "edl_train_step_seconds", "Wall time between completed train steps")
_STEPS_TOTAL = obs_metrics.counter(
    "edl_train_steps_total", "Completed train steps")
_EXAMPLES_TOTAL = obs_metrics.counter(
    "edl_train_examples_total", "Examples consumed (global batch rows)")
_EPOCHS_TOTAL = obs_metrics.counter(
    "edl_train_epochs_total", "Completed epochs")

# live MFU: XLA cost-analysis FLOPs (obs/flops.py) over the step-time
# EMA, published continuously so utilization is a scrape away
_TFLOPS_G = obs_metrics.gauge(
    "edl_tflops_per_chip",
    "Achieved TFLOP/s per chip from XLA cost analysis over the "
    "step-time EMA (train/trainer.py, obs/flops.py)")
_MFU_G = obs_metrics.gauge(
    "edl_mfu",
    "Model FLOPs utilization: edl_tflops_per_chip / the chip's known "
    "bf16 peak (EDL_TPU_PEAK_TFLOPS overrides; absent when the device "
    "kind is unknown)")

# loss_fn(params, extra, batch, rng) -> (loss, (new_extra, metrics))
LossFn = Callable[[Any, Any, Any, jax.Array], tuple[jax.Array, tuple[Any, dict]]]


# abandoned pre-reshard checkpoint managers: referenced forever so GC
# can never run their teardown (which may barrier against a dead world)
_ABANDONED_CKPTS: list = []


@dataclass
class _ReshardPayload:
    """Host-side hand-off from the epoch loop to the live reshard:
    nothing in here may reference a device array (the old backend is
    about to be torn down)."""

    mode: str                    # "grow" (paused+saved) | "shrink" (rollback)
    local: dict | None = None    # {key: (manifest_entry, bytes_view)} at step
    step: int | None = None      # the paused step (grow only)


class _LiveReshard(Exception):
    """Raised at a step boundary to unwind the epoch loop into
    ``ElasticTrainer._live_reshard`` (EDL_TPU_RESIZE_DELTA=1): the
    process survives the membership change and re-forms the collective
    world in place instead of dying into a stop-resume."""

    def __init__(self, payload: _ReshardPayload):
        super().__init__(payload.mode)
        self.payload = payload


@dataclass
class _LeafSpec:
    """Mesh-independent skeleton of one state leaf (deliberately NOT a
    registered pytree — it must ride tree.map as a leaf): enough to
    rebuild the abstract restore target against ANY new mesh."""

    shape: tuple
    dtype: Any
    spec: Any                    # PartitionSpec (mesh-free by design)


@dataclass
class TrainConfig:
    mesh_spec: MeshSpec = field(default_factory=MeshSpec)
    rules: ShardingRules = field(default_factory=ShardingRules)
    checkpoint_dir: str = ""
    save_every_steps: int = 0          # 0 = per-epoch only (reference default)
    max_to_keep: int = 3
    log_every: int = 100
    global_batch_size: int = 0
    near_end_epochs: int = 1           # NEARTHEEND window (train_status.py:22-27)
    # overlap host->device staging of batch i+1 with step i (the
    # reference got this from DALI's pipelined stages); 0 disables
    prefetch_batches: int = 1
    # rank-0 profiler window [start_step, stop_step], reference
    # train_with_fleet.py:521-530 profiled batches 100-105
    profile_window: tuple[int, int] | None = None
    profile_dir: str = ""
    # liveness beat to the coordination store after completed steps
    # (throttled to this period; consumed by the launcher's hang
    # watchdog, EDL_TPU_HANG_TIMEOUT); 0 disables
    heartbeat_every: float = 10.0


class ElasticTrainer:
    def __init__(self, loss_fn: LossFn, config: TrainConfig | None = None,
                 store=None, tenv: TrainerEnv | None = None, devices=None):
        self.cfg = config or TrainConfig()
        self.loss_fn = loss_fn
        # env-gated (EDL_TPU_METRICS_PORT / EDL_TPU_TRACE_DIR): trainers
        # are user scripts with no CLI entry point of ours, so the
        # trainer is where the per-process observability surfaces attach
        from edl_tpu import obs
        obs.install_from_env("trainer")
        # SIGUSR1 -> all-thread stack dump on stderr (the workerlog):
        # the first diagnostic anyone needs for a trainer that hangs in
        # a collective — the hang watchdog can only say THAT it hangs
        try:
            import faulthandler
            import signal as _signal
            faulthandler.register(_signal.SIGUSR1, all_threads=True)
        except (ImportError, AttributeError, ValueError):
            pass  # non-main thread / platform without SIGUSR1
        if tenv is not None and tenv.pod_id:
            # under the launcher, stderr IS the workerlog: install the
            # edl_tpu log handler (idempotent) so restore/preempt/
            # heartbeat INFO lines — restore_source above all — reach
            # the operator instead of dying in logging.lastResort
            from edl_tpu.utils import logger as _logger_mod
            _logger_mod.configure()
        self.tenv = tenv
        self.store = store
        # under the launcher (store + pod identity known): advertise
        # this trainer's /metrics endpoint so edl-obs-agg discovers it
        self._obs_register = None
        if store is not None and tenv is not None and tenv.pod_id:
            from edl_tpu.obs import advert as obs_advert
            self._obs_register = obs_advert.advertise_installed(
                store, tenv.job_id, "trainer", extra={"pod": tenv.pod_id})
        self.mesh = build_mesh(self.cfg.mesh_spec, devices)
        dev = self.mesh.devices.flat[0]
        logger.info("devices: platform=%s device_kind=%s count=%d mesh=%s",
                    dev.platform, dev.device_kind, self.mesh.devices.size,
                    dict(self.mesh.shape))
        self.rules = self.cfg.rules
        self.adjust = AdjustRegistry()
        # delta replication plane (memstate/delta.py): owned here, built
        # alongside the checkpoint manager and rebuilt with it on reshard
        self._delta_rep = None
        self.ckpt = self._build_ckpt()
        self._step_fn = None
        self._t_restored: float | None = None  # recovery instrumentation
        self._builds_at_restored: dict = {}
        self._restore_source: str | None = None  # "peer"|"storage"|"delta"
        # delta-resize machinery (EDL_TPU_RESIZE_DELTA): the launcher's
        # resize flag is polled on the preempt cadence; _state_spec is
        # the mesh-free skeleton a live reshard rebuilds against
        self._reshard_seen = False
        self._state_spec = None
        # per-step phase ledger (its phases are the train/<phase> spans
        # of a profiler capture) + the on-demand capture it backs on
        # CPU; /profile rides the same endpoint the process already
        # advertises for /metrics
        self._ledger = obs_ledger.StepPhaseLedger(component="train")
        self._profiler = obs_profile.ProfileCapture("trainer",
                                                    ledger=self._ledger)
        obs_profile.install_route(self._profiler)
        # live MFU: FLOPs/step from XLA cost analysis, computed once per
        # compiled step function (invalidated with _step_fn on reshard)
        self._flops_per_step: float | None = None
        self._mfu_denom: tuple[float | None, int] = (None, 1)
        # id -> (metric_fn, jitted): holding metric_fn pins its id so a
        # recycled id can never alias a different function; bounded so
        # fresh closures per call can't leak jitted executables forever
        self._eval_cache: OrderedDict[int, tuple[Any, Any]] = OrderedDict()

    def _build_ckpt(self) -> CheckpointManager | None:
        """Construct the checkpoint manager (+ memstate tee).  Called at
        init AND after every live reshard: in a multiprocess world the
        manager's construction runs a world-wide sync, so survivors must
        construct a FRESH one right after re-forming the world — pairing
        with the construction sync of any freshly spawned joiner."""
        if self._delta_rep is not None:
            # an old replicator targets the OLD membership's chains;
            # signal-only close — never block a reshard on a dead peer
            self._delta_rep.close(wait=False)
            self._delta_rep = None
        if not self.cfg.checkpoint_dir:
            return None
        # under the elastic launcher, committed saves tee into the pod's
        # in-RAM peer checkpoint cache (memstate) so a post-resize
        # restore can come from surviving hosts instead of storage
        tee = None
        if self.store is not None and self.tenv is not None \
                and self.tenv.pod_id:
            from edl_tpu import memstate
            if memstate.enabled():
                try:
                    tee = memstate.StateCacheTee(self.store,
                                                 self.tenv.job_id,
                                                 self.tenv.pod_id)
                except Exception:  # noqa: BLE001 — cache is best-effort
                    logger.exception("memstate tee unavailable")
            if tee is not None and memstate.delta_enabled():
                try:
                    self._delta_rep = memstate.DeltaReplicator(
                        self.store, self.tenv.job_id, self.tenv.pod_id)
                except Exception:  # noqa: BLE001 — deltas are best-effort
                    logger.exception("delta replicator unavailable")
        return CheckpointManager(self.cfg.checkpoint_dir,
                                 self.cfg.max_to_keep, tee=tee)

    # -- state construction --------------------------------------------------
    def _build_fn(self, init_fn, tx, param_logical):
        from edl_tpu.utils import constants as _c
        if _c.LR_RESCALE:
            # first-class world-derived LR re-scale: every state built
            # through this one choke point (create_state AND the restore
            # skeleton) carries the world-scale stage, so the structure
            # is consistent across save/restore.  Default OFF because it
            # CHANGES the opt_state pytree — flipping it mid-run makes
            # old checkpoints structurally unrestorable.
            from edl_tpu.train import lr as lr_mod
            tx = lr_mod.world_scaled(tx)
        mesh, rules = self.mesh, self.rules

        def constrain(params):
            if param_logical is None:
                repl = NamedSharding(mesh, P())
                return jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(x, repl), params)
            logical = _merge_logical(
                jax.tree.map(lambda _: (None,), params), param_logical)
            # params is the structure tree: flatten_up_to stops at array
            # leaves, so logical's axes-tuples arrive whole
            return jax.tree.map(
                lambda x, ax: jax.lax.with_sharding_constraint(
                    x, logical_sharding(ax, mesh, rules)),
                params, logical)

        def build():
            import jax.numpy as jnp
            params, extra = init_fn()
            params = constrain(params)
            opt_state = _map_params_like(tx.init(params), params, constrain)
            return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                              opt_state=opt_state, tx=tx, extra=extra)

        return build

    def create_state(self, init_fn: Callable[[], tuple[Any, Any]],
                     tx, param_logical=None) -> TrainState:
        """Build a TrainState with parameters born sharded.

        ``init_fn() -> (params, extra)``; ``param_logical`` is a pytree of
        logical-axes tuples matching params (None → fully replicated, the
        reference's DP layout).  Sharding is constrained *inside* the
        jitted init so ``tx.init`` inherits it and the optimizer state
        (momenta) comes out sharded like its parameters — the FSDP
        memory win falls out of propagation, not bookkeeping.  The
        program-build ledger's ``setup/train/state`` (``obs/ledger.py``),
        waited for, so that the span holds the init's run."""
        with _BUILDS.setup("train", key="create"):
            return jax.block_until_ready(
                jax.jit(self._build_fn(init_fn, tx, param_logical))())

    def _abstract_state(self, init_fn, tx, param_logical) -> TrainState:
        """Shape/dtype/sharding skeleton WITHOUT materialising arrays, so
        a restore never pays init memory (AOT-compile the init to learn
        the output shardings); falls back to materialise-and-discard."""
        build = self._build_fn(init_fn, tx, param_logical)
        try:
            compiled = jax.jit(build).lower().compile()
            shardings = compiled.output_shardings
            shapes = jax.eval_shape(build)
            return jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                shapes, shardings)
        except Exception:  # noqa: BLE001 — AOT introspection unavailable
            logger.exception("AOT abstract state failed; materialising init")
            return abstract_like(jax.jit(build)())

    def restore_or_create(self, init_fn, tx, param_logical=None,
                          ) -> tuple[TrainState, State]:
        meta = State(total_batch_size=self.cfg.global_batch_size)
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return self.create_state(init_fn, tx, param_logical), meta
        latest = self.ckpt.latest_step()
        with _BUILDS.setup("train", key="restore"):
            abstract = self._abstract_state(init_fn, tx, param_logical)
            state, saved_meta = self._cache_first_restore(abstract, latest)
            if state is None:
                from edl_tpu.memstate.restore import RESTORE_SECONDS
                t0 = time.perf_counter()
                with obs_trace.get_tracer().span("train/restore",
                                                 step=latest):
                    restored = self.ckpt.restore(abstract)
                assert restored is not None
                state, saved_meta = restored
                self._restore_source = "storage"
                RESTORE_SECONDS.labels(source="storage").observe(
                    time.perf_counter() - t0)
                logger.info("restored step %d from storage (restore_source="
                            "storage, %.1fs)", latest,
                            time.perf_counter() - t0)
        if saved_meta is not None:
            meta = saved_meta
        # recovery-time instrumentation: the ``restored`` stamp of the
        # resize record and what the program-build ledger stood at then
        # (_report_recovery hands over what was built up to the first step)
        self._t_restored = time.time()
        self._builds_at_restored = _BUILDS.totals()
        old_world = _last_world(meta)
        new_world = self.world_size
        if old_world and old_world != new_world:
            logger.info("world size %d -> %d; running adjust functions",
                        old_world, new_world)
            self.adjust.run(old_world, new_world, meta)
            state = self._world_lr_rescale(state, old_world, new_world)
        if self._delta_rep is not None and self._restore_source is not None:
            # re-anchor the delta chain on the restored step when it IS
            # the committed one; a chain-overlay restore lands past the
            # commit, so its chain stays useful until the next save
            if self._restore_source != "delta":
                self._delta_rep.rebase(int(state.step), state)
        return state, meta

    def _world_lr_rescale(self, state, old_world: int, new_world: int):
        """EDL_TPU_LR_RESCALE: linear LR-vs-global-batch adjustment on
        a world change — multiplies the world-scale stage riding the
        optimizer state (train/lr.py) by new/old.  No-op tree when the
        optimizer was not built with the knob on."""
        from edl_tpu.utils import constants as _c
        if not _c.LR_RESCALE or not old_world or old_world == new_world:
            return state
        from edl_tpu.train import lr as lr_mod
        factor = new_world / old_world
        logger.info("LR rescale: world %d -> %d, effective-LR factor %.3f",
                    old_world, new_world, factor)
        return lr_mod.rescale_state(state, factor)

    def _cache_first_restore(self, abstract, latest: int
                             ) -> tuple[Any, State | None]:
        """Try the peer checkpoint cache (memstate) before storage:
        fetch shards from surviving pods' RAM, reassemble to THIS
        mesh's shardings, verify CRCs and that the cached step matches
        both the coord store's committed record and storage's latest.
        ``(None, None)`` on any miss — the caller falls back to the
        Orbax path.  EDL_TPU_MEMSTATE_VERIFY=1 additionally restores
        from storage and asserts bit-identity (e2e proof hook)."""
        if self.store is None or self.tenv is None or not self.tenv.pod_id:
            return None, None
        from edl_tpu import memstate
        if not memstate.enabled():
            return None, None
        from edl_tpu.memstate import restore as ms_restore
        t0 = time.perf_counter()
        # sub-checkpoint-loss failover: restore base + the freshest
        # intact delta chains when the whole world agrees one is
        # reachable; any per-process failure demotes EVERY process to
        # the plain committed-step restore (a torn mix of steps across
        # processes would be worse than the lost interval)
        delta_step = self._agree_delta_target(latest)
        res = None
        if delta_step is not None:
            try:
                with obs_trace.get_tracer().span("train/restore_delta",
                                                 step=delta_step):
                    res = ms_restore.try_restore(
                        self.store, self.tenv.job_id, abstract,
                        expect_step=latest, delta_step=delta_step)
            except Exception:  # noqa: BLE001 — demote to the base restore
                logger.exception("delta-chain restore errored")
            if not self._agree_flag(res is not None):
                res = None  # someone missed: everyone takes the base
        source = "delta" if res is not None else "peer"
        if res is None:
            try:
                with obs_trace.get_tracer().span("train/restore_peer",
                                                 step=latest):
                    res = ms_restore.try_restore(self.store,
                                                 self.tenv.job_id,
                                                 abstract,
                                                 expect_step=latest)
            except Exception:  # noqa: BLE001 — cache never fails a restore
                logger.exception("peer-cache restore errored; using storage")
                return None, None
        if res is None:
            return None, None
        state, meta_json, info = res
        meta = State().from_json(meta_json)
        if os.environ.get("EDL_TPU_MEMSTATE_VERIFY") == "1" \
                and info["step"] == latest:
            # only comparable when the restored step IS the storage
            # step; a chain-overlay restore is fresher than storage by
            # construction (the failover smoke verifies it end to end)
            stored = self.ckpt.restore(abstract)
            assert stored is not None
            ms_restore.assert_bit_identical(state, stored[0])
            logger.info("memstate: peer restore verified bit-identical to "
                        "storage (step %d)", latest)
        self._restore_source = source
        ms_restore.RESTORE_SECONDS.labels(source=source).observe(
            time.perf_counter() - t0)
        logger.info("restored step %d from peer cache (restore_source=%s, "
                    "%d shards, %.1f MB from %s)", info["step"], source,
                    info["shards"], info["bytes"] / 1e6,
                    [p[:8] for p in info["peers"]])
        return state, meta

    def _agree_delta_target(self, expect: int | None) -> int | None:
        """The world-agreed delta restore target past ``expect`` (the
        committed/storage step), or None.  Every process probes the
        freshest recoverable step (memstate.probe_freshest) and the
        allgathered MIN is the answer — restorable by construction on
        every process (intact chains are prefix-closed), identical
        everywhere, and -1 from any process (probe failure, stale
        committed record, nothing fresher) demotes the whole world.
        The collective is UNCONDITIONAL on the delta knob being on, so
        every process must call this at the same point."""
        from edl_tpu import memstate
        if not memstate.delta_enabled():
            return None
        committed = freshest = None
        try:
            committed, freshest = memstate.probe_freshest(
                self.store, self.tenv.job_id)
        except Exception:  # noqa: BLE001 — probe failure = no delta
            logger.exception("delta freshness probe failed")
        cand = -1
        if (expect is not None and committed == expect
                and freshest is not None and freshest > expect):
            cand = int(freshest)
        if jax.process_count() > 1:
            from edl_tpu.parallel.sharding import allgather_flag
            cand = int(allgather_flag(cand).min())
        if cand <= (expect if expect is not None else cand):
            return None
        logger.info("delta restore target agreed: step %d (base %s)",
                    cand, expect)
        return cand

    @staticmethod
    def _agree_flag(ok: bool) -> bool:
        """All-processes-AND of a local outcome (identity when solo)."""
        if jax.process_count() <= 1:
            return bool(ok)
        from edl_tpu.parallel.sharding import allgather_flag
        return bool(allgather_flag(int(bool(ok))).min())

    # -- the step ------------------------------------------------------------
    def _make_step(self):
        loss_fn = self.loss_fn

        def step(state: TrainState, batch, rng):
            def lf(p):
                return loss_fn(p, state.extra, batch, rng)
            (loss, (new_extra, metrics)), grads = jax.value_and_grad(
                lf, has_aux=True)(state.params)
            new_state = state.apply_gradients(grads, new_extra)
            metrics = dict(metrics or {})
            metrics["loss"] = loss
            return new_state, metrics

        # the first call is the span build/train/step of the
        # program-build ledger; _step_fn is the bare jitted step after it
        return _BUILDS.first_call(jax.jit(step, donate_argnums=(0,)),
                                  "train", "step", None, self, "_step_fn")

    @property
    def step_fn(self):
        if self._step_fn is None:
            self._step_fn = self._make_step()
        return self._step_fn


    @property
    def world_size(self) -> int:
        return jax.process_count() if jax.process_count() > 1 else batch_divisor(self.mesh)

    # -- the loop ------------------------------------------------------------
    def fit(self, state: TrainState, meta: State,
            data_fn: Callable[[int], Iterable[Any]], epochs: int,
            rng: jax.Array | None = None,
            on_epoch_end: Callable[[int, TrainState, State], None] | None = None,
            ) -> tuple[TrainState, State]:
        """Run epochs ``meta.next_epoch .. epochs-1``; each ``data_fn(e)``
        yields host-local numpy batches.  ``on_epoch_end`` runs after the
        epoch checkpoint commits (eval pass, benchmark dump — the
        reference's per-epoch test hook, train_with_fleet.py:642-658);
        anything it writes into ``meta`` is patched into that same
        epoch's committed sidecar afterwards.  Returns the final state."""
        rng = jax.random.key(0) if rng is None else rng
        if self._run_t0 is None:
            self._run_t0 = time.monotonic()
        self._report(TrainStatus.RUNNING)
        self._capture_state_spec(state)
        while True:
            payload = crash = None
            try:
                for epoch in range(meta.next_epoch, epochs):
                    if epochs - epoch <= self.cfg.near_end_epochs:
                        self._report(TrainStatus.NEARTHEEND)
                    # per-epoch fold so dropout/augmentation differ
                    # across epochs
                    state, meta = self._run_epoch(
                        state, meta, data_fn, epoch,
                        jax.random.fold_in(rng, epoch), on_epoch_end)
                break
            except _LiveReshard as sig:
                payload = sig.payload
            except Exception as exc:  # noqa: BLE001 — maybe a dying peer
                # a peer pod's death surfaces as a failed collective
                # seconds before the membership change is visible; with
                # the delta path on, convert the crash into a rollback
                # reshard instead of dying into a stop-resume.  The
                # traceback is formatted then DROPPED: its frames pin
                # the epoch's device arrays, which pin the old backend,
                # whose open sockets keep blocked peers hanging
                if not self._delta_ready():
                    raise
                import traceback as _tb
                crash = "".join(_tb.format_exception(
                    type(exc), exc, exc.__traceback__))
                # clear the WHOLE cause/context chain: any link's
                # traceback pins the failing frames just as well
                link, hops = exc, 0
                while link is not None and hops < 20:
                    link.__traceback__ = None
                    nxt = link.__cause__ or link.__context__
                    link.__cause__ = link.__context__ = None
                    link, hops = nxt, hops + 1
                crash_exc = exc
            # nothing below may hold device arrays: the payload is
            # host-side and the except blocks above released their
            # frames.  The rng crosses the teardown as host bytes
            try:
                rng_data, typed_key = np.asarray(
                    jax.random.key_data(rng)), True
            except Exception:  # noqa: BLE001 — old-style raw uint32 key
                rng_data, typed_key = np.asarray(rng), False
            state = rng = None
            if crash is not None:
                payload = self._reshard_on_failure(crash_exc, crash)
            state, meta = self._live_reshard(payload, meta)
            rng = (jax.random.wrap_key_data(jax.numpy.asarray(rng_data))
                   if typed_key else jax.numpy.asarray(rng_data))
        if self.ckpt is not None:
            self.ckpt.wait()
        self._report(TrainStatus.SUCCEED)
        return state, meta

    def _run_epoch(self, state, meta, data_fn, epoch, rng, on_epoch_end=None):
        t_epoch, n_steps = time.monotonic(), 0
        start_step = int(state.step)  # one sync per epoch, not per step
        if meta.in_epoch != epoch:
            # entering fresh (not a mid-epoch resume): reset the data
            # checkpoint so mid-epoch saves this epoch start from zero
            meta.in_epoch = epoch
            meta.epoch_start_step = start_step
            meta.data_checkpoint = DataCheckpoint()
        ledger = self._ledger
        stream = iter(self._sharded_stream(data_fn(epoch)))
        while True:
            # time blocked obtaining the batch — input-bound time; the
            # h2d staging wait inside the stream credits itself and is
            # deducted, so data_wait is the prefetch-ran-dry remainder
            with ledger.phase("data_wait"):
                item = next(stream, None)
            if item is None:
                break
            gbatch, spans = item
            with ledger.phase("hooks"):
                if spans:
                    # batches from the data service carry their record
                    # spans; marking HERE (not at production/prefetch
                    # time) keeps mid-epoch checkpoints exactly
                    # consistent with what has actually been trained,
                    # whatever the prefetch depth
                    for fi, b, e in spans:
                        meta.data_checkpoint.mark_processed(fi, b, e)
                self._profile_hook(start_step + n_steps + 1)
                rng, step_rng = jax.random.split(rng)
            with ledger.phase("compute"):
                state, metrics = self.step_fn(state, gbatch, step_rng)
            n_steps += 1
            step = start_step + n_steps
            self._observe_step_time(step)
            with ledger.phase("hooks"):
                _STEPS_TOTAL.inc()
                # global batch rows, counted by process 0 only: scrapes
                # are per-process and Prometheus sums across targets, so
                # every process counting the GLOBAL dimension would
                # overcount by the process count
                if jax.process_index() == 0:
                    leaves = jax.tree.leaves(gbatch)
                    if leaves and getattr(leaves[0], "shape", None):
                        _EXAMPLES_TOTAL.inc(int(leaves[0].shape[0]))
                if self._flops_per_step is None:
                    self._compute_flops(state, gbatch, step_rng)
                    self._log_device_memory()
                if self._t_restored is not None:
                    self._report_recovery(metrics)
                self._heartbeat()
                self._maybe_preempt(state, meta, step)
                if self.cfg.log_every and step % self.cfg.log_every == 0:
                    logger.info("epoch %d step %d: %s", epoch, step,
                                {k: float(v) for k, v in metrics.items()})
                if self._profiling and step >= self.cfg.profile_window[1]:
                    self._stop_profile()
            saving = (self.ckpt is not None and self.cfg.save_every_steps
                      and step % self.cfg.save_every_steps == 0)
            if (not saving and self._delta_rep is not None
                    and self._delta_rep.want(step)):
                # stream a delta record for this step (D2H + push on the
                # worker thread; only the snapshot is on the step path).
                # ``want`` is deterministic across processes, so the
                # collective _sync_data_checkpoint below stays aligned
                with ledger.phase("hooks"):
                    meta.step = step
                    self._sync_data_checkpoint(meta)
                    self._delta_rep.stage(step, state, meta)
            if saving:
                with ledger.phase("checkpoint"):
                    meta.step = step
                    self._sync_data_checkpoint(meta)
                    self.ckpt.save(step, state, meta)
                    if self._delta_rep is not None:
                        # new base: re-anchor the chain on this commit
                        self._delta_rep.rebase(step, state)
        dt = time.monotonic() - t_epoch
        # step_num covers the WHOLE epoch, including segments trained
        # before a mid-epoch stop-resume; avg time reflects this segment
        total_steps = (start_step + n_steps) - meta.epoch_start_step
        meta.record_epoch(epoch, self.world_size, total_steps,
                          dt / max(1, n_steps))
        meta.step = start_step + n_steps
        meta.epoch_no = epoch
        meta.in_epoch = -1  # epoch complete: next resume starts the next one
        ledger.flush(step=start_step + n_steps)
        if self.ckpt is not None:
            with ledger.phase("checkpoint"):
                self._sync_data_checkpoint(meta)
                if (self.cfg.save_every_steps
                        and self.ckpt.latest_step() == int(state.step)):
                    # the last mid-epoch save already committed this
                    # step's arrays; just patch its sidecar with the
                    # end-of-epoch accounting (in_epoch=-1, the record)
                    self.ckpt.save_meta(int(state.step), meta)
                else:
                    self.ckpt.save(int(state.step), state, meta, force=True)
                    if self._delta_rep is not None:
                        self._delta_rep.rebase(int(state.step), state)
                # Under the elastic launcher a membership change SIGTERMs
                # the trainer between epochs; drain the async save so the
                # resize never lands before any checkpoint committed (a
                # killed pending save would cold-start the resized job,
                # losing all progress).  Standalone runs keep saves
                # fully async.
                if self.tenv is not None and self.tenv.pod_id:
                    self.ckpt.wait()
        if on_epoch_end is not None:
            # The epoch checkpoint is committed FIRST so a SIGTERM during
            # the hook (a long eval pass) can't lose the epoch's training;
            # hook mutations of ``meta`` (bench/eval records) are then
            # patched into the committed sidecar, cheap vs re-saving arrays.
            before = meta.to_json()
            on_epoch_end(epoch, state, meta)
            if self.ckpt is not None and meta.to_json() != before:
                self.ckpt.save_meta(int(state.step), meta)
        if self._profiling:  # epoch ended inside the window
            self._stop_profile()
        _EPOCHS_TOTAL.inc()
        obs_trace.emit("train/epoch", dur=dt, epoch=epoch, steps=n_steps,
                       world=self.world_size)
        logger.info("epoch %d done: %d steps in %.1fs", epoch, n_steps, dt)
        return state, meta

    # -- input prefetch ------------------------------------------------------
    def _sharded_stream(self, batches: Iterable[Any]):
        """Yield ``(global_batch, consumed_spans)``, staging batch i+1
        while the device runs step i (host decode + H2D never serialize
        with compute — the DALI-style double buffering the reference
        relied on).  Depth is fixed at one batch so the collective order
        of any data_fn internals (the data service's has-next agreement)
        stays identical on every process.  Span marking stays with the
        CONSUMER (the epoch loop), so prefetching can never checkpoint a
        span ahead of the training step that uses it."""
        def split(batch):
            spans = None
            if isinstance(batch, dict) and _SPANS_KEY in batch:
                batch = dict(batch)
                spans = batch.pop(_SPANS_KEY)
            return batch, spans

        ledger = self._ledger
        if not self.cfg.prefetch_batches:
            for batch in batches:
                batch, spans = split(batch)
                with ledger.phase("h2d"):
                    g = shard_host_batch(batch, self.mesh, self.rules)
                yield g, spans
            return
        from concurrent.futures import ThreadPoolExecutor

        def staged(fut):
            # the wait for the staging thread IS the unhidden host->
            # device time; it runs inside the consumer's data_wait
            # phase and is deducted from it
            with ledger.phase("h2d"):
                return fut.result()

        with ThreadPoolExecutor(1) as pool:
            fut = None
            for batch in batches:
                batch, spans = split(batch)
                nxt = (pool.submit(shard_host_batch, batch, self.mesh,
                                   self.rules), spans)
                if fut is not None:
                    yield staged(fut[0]), fut[1]
                fut = nxt
            if fut is not None:
                yield staged(fut[0]), fut[1]

    # -- profiler window (reference train_with_fleet.py:521-530) -------------
    _profiling = False

    def _profile_hook(self, upcoming_step: int) -> None:
        w = self.cfg.profile_window
        if (w is None or self._profiling or jax.process_index() != 0
                or upcoming_step != w[0]):
            return
        out = self.cfg.profile_dir or "/tmp/edl-tpu-profile"
        logger.info("profiler: tracing steps %d-%d to %s", w[0], w[1], out)
        jax.profiler.start_trace(out)
        self._profiling = True

    def _stop_profile(self) -> None:
        try:
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 — profiling must never fail a run
            logger.exception("profiler stop failed")
        self._profiling = False

    def _report_recovery(self, metrics) -> None:
        """Trainer half of the resize timing record: checkpoint restored
        and the first post-restart step finished.  The launcher wrote
        detect/kill/barrier/spawn under the same stage key; the merged
        record is the north-star recovery-time metric (BASELINE.md)."""
        t_restored, self._t_restored = self._t_restored, None
        if (self.store is None or self.tenv is None
                or not self.tenv.cluster_stage
                or self.tenv.rank_in_pod != 0):
            return
        jax.block_until_ready(metrics["loss"])  # the step truly finished
        try:
            from edl_tpu.cluster import recovery
            # unified write: store record + resize-phase histogram +
            # trace events from one times dict (recovery.py)
            recovery.write_trainer_half(
                self.store, self.tenv.job_id, self.tenv.cluster_stage,
                self.tenv.pod_id, restored=t_restored,
                first_step=time.time(),
                restore_source=self._restore_source,
                builds=recovery.build_fields(self._builds_at_restored,
                                             _BUILDS.totals()))
        except Exception:  # noqa: BLE001 — metrics must never fail a job
            logger.exception("recovery record write failed")

    _last_beat = 0.0
    _last_step_t: float | None = None
    _step_ema: float | None = None
    _run_t0: float | None = None
    _warned_no_beat = False

    def _observe_step_time(self, step: int | None = None) -> None:
        """EMA of the wall time between completed-step observations.
        Steps dispatch asynchronously, but with a bounded dispatch
        queue the steady-state loop rate equals the device step rate,
        so the EMA converges on the true step time (the first gaps —
        compile — are absorbed by the EMA and the threshold floor).
        Also closes the step's phase ledger against the interval and
        refreshes the live MFU gauges."""
        now = time.monotonic()
        if self._last_step_t is not None:
            dt = now - self._last_step_t
            self._step_ema = (dt if self._step_ema is None
                              else 0.9 * self._step_ema + 0.1 * dt)
            _STEP_SECONDS.observe(dt)
            self._ledger.step_done(dt, step=step)
            self._publish_mfu()
        else:
            # first observation (fresh run / post-reshard): no interval
            # exists, and the phases accumulated so far include the jit
            # compile — discard them instead of attributing a
            # compile-sized "compute" sample to the next step
            self._ledger.reset()
        self._last_step_t = now

    def _compute_flops(self, state, gbatch, rng) -> None:
        """FLOPs of one compiled step from XLA cost analysis — once per
        step function (obs/flops.py).

        Runs on a BACKGROUND daemon thread: the AOT ``lower().compile()``
        path does not share the jit dispatch cache (measured: a full
        recompile), so on a big model it can cost a real compile — that
        must never stall the train loop (or be booked as a giant hooks
        phase).  The thread sees only ``ShapeDtypeStruct`` skeletons,
        never device arrays — but its reference to the jitted function
        itself pins compiled executables, and so the backend.  For a
        stop-resume trainer that is harmless (teardown is process
        death); a DELTA-capable trainer must be able to truly destroy
        its old backend mid-reshard (train/distributed.leak_world —
        peers hang on our open gloo sockets otherwise), and a thread
        mid-compile cannot be swept.  So live MFU is skipped when the
        delta path is armed — phase ledger and goodput still run.  The
        result lands only if the step function is still the one it was
        computed for.  Gated with the ledger (a test's
        ``enabled=False``); 0.0 = pending-or-unanswerable, so there is
        no per-step retry."""
        self._flops_per_step = 0.0
        if not self._ledger.enabled or self._delta_ready():
            return
        jitted = self.step_fn

        def skel(x):
            if hasattr(x, "shape") and hasattr(x, "sharding"):
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=x.sharding)
            return x

        try:
            args = jax.tree.map(skel, (state, gbatch, rng))
        except Exception:  # noqa: BLE001 — no MFU, never a stall
            logger.exception("MFU arg skeleton failed; live MFU disabled")
            return

        def run():
            # booked to THIS thread's span, not to what the loop has open
            with _BUILDS.build("train", "step_flops"):
                flops = obs_flops.xla_cost_flops(jitted, *args)
            try:
                denom = (obs_flops.peak_tflops(jax.devices()[0]),
                         jax.device_count())
            except Exception:  # noqa: BLE001 — no backend, no MFU
                denom = (None, 1)
            if flops and self._step_fn is jitted:
                self._mfu_denom = denom
                self._flops_per_step = flops
                # publish immediately too: a short job may finish its
                # last step before this thread lands
                self._publish_mfu()

        import threading
        threading.Thread(target=run, daemon=True,
                         name="edl-mfu-cost-analysis").start()

    def _log_device_memory(self) -> None:
        """Once per compiled step function: what every local device
        holds after a real step (state + batch + workspace) — the HBM
        headroom figure, and the proof that a mesh spread the job."""
        held = {d.id: d.memory_stats() for d in jax.local_devices()}
        if all(held.values()):   # CPU backends report nothing
            logger.info("device memory after step (MiB in use / peak): %s",
                        {i: (s["bytes_in_use"] >> 20,
                             s["peak_bytes_in_use"] >> 20)
                         for i, s in held.items()})

    def _publish_mfu(self) -> None:
        if not (self._flops_per_step and self._step_ema):
            return
        peak, n_dev = self._mfu_denom
        tflops = (self._flops_per_step / self._step_ema
                  / max(1, n_dev) / 1e12)
        _TFLOPS_G.set(tflops)
        if peak:
            _MFU_G.set(tflops / peak)

    def _heartbeat(self) -> None:
        """Throttled liveness beat after a completed step (rank 0 in
        the pod) — feeds the launcher's hang watchdog.  The first beat
        only happens after step 1 finishes, so the watchdog can never
        mistake the initial XLA compile for a hang.  Publishes the
        self-derived stale threshold (max(10x EMA step, 120 s); the
        first beat, before any inter-step interval exists, uses 10x the
        elapsed wall time since fit() began so slow-step jobs are never
        false-killed in the step-1..2 window) so the watchdog is on by
        default with no tuning.  Best-effort."""
        if (self.store is None or self.tenv is None or not self.tenv.pod_id
                or self.tenv.rank_in_pod != 0):
            return
        from edl_tpu.utils import constants as _c
        if not self.cfg.heartbeat_every:
            # heartbeat disabled while the watchdog is enabled (auto,
            # the default, or an explicit HANG_TIMEOUT>0): the launcher
            # would (correctly) never engage — say so loudly once,
            # because the docs promise on-by-default hang protection
            if _c.HANG_TIMEOUT >= 0 and not self._warned_no_beat:
                self._warned_no_beat = True
                logger.warning(
                    "heartbeat_every=0 disables the liveness beat, so the "
                    "hang watchdog (EDL_TPU_HANG_TIMEOUT=%s%s) never "
                    "engages for this trainer", _c.HANG_TIMEOUT,
                    " = auto" if _c.HANG_TIMEOUT == 0 else "")
            return
        # step-time EMA is maintained by the epoch loop's per-step
        # _observe_step_time() call (shared with the step metrics)
        from edl_tpu.cluster import heartbeat
        threshold = None
        if _c.HANG_TIMEOUT == 0:
            # first beat (no inter-step interval observed yet): the bare
            # floor would false-kill any job whose steady step exceeds
            # it, so feed the elapsed wall time since fit() began
            # (compile + step 1, an upper bound on step time) into the
            # same auto_threshold formula; the second beat replaces it
            # with the EMA-derived value.
            ema = self._step_ema
            if ema is None and self._run_t0 is not None:
                ema = time.monotonic() - self._run_t0
            threshold = heartbeat.auto_threshold(ema)
        # auto-couple the throttle: beat at least 3x faster than the
        # effective stale threshold, whatever heartbeat_every says — a
        # threshold below the throttle must never kill a healthy trainer
        every = self.cfg.heartbeat_every
        effective = _c.HANG_TIMEOUT if _c.HANG_TIMEOUT > 0 else threshold
        if effective:
            every = min(every, effective / 3.0)
        now = time.monotonic()
        if now - self._last_beat < every:
            return
        self._last_beat = now
        try:
            heartbeat.beat(self.store, self.tenv.job_id, self.tenv.pod_id,
                           threshold=threshold)
        except Exception:  # noqa: BLE001 — liveness must never fail a job
            logger.exception("heartbeat write failed")

    _preempt_seen = False
    _preempt_next_check: int | None = None   # agreed next check step (multi)
    _preempt_last_check_t = 0.0              # wall clock of last check (solo)

    def _maybe_preempt(self, state, meta, step: int) -> None:
        """SIGTERM-preemption grace (cluster/preempt.py): at a
        step-aligned cadence, check the stage's preempt flag; in a
        multi-process world OR the sightings via a tiny allgather so
        EVERY process picks the SAME step (the save is collective).
        On agreement: checkpoint (state + data spans) at this exact
        step and exit PREEMPT_EXIT_CODE — the launcher reads that as a
        clean coordinated departure, survivors resume from this
        checkpoint with no span reprocessed.

        Cadence (ADVICE r5): the check costs a store read + a world
        allgather, so it runs on a WALL-CLOCK cadence
        (~PREEMPT_CHECK_SECONDS), not a fixed step count — a fixed
        every-8-steps collective taxed millisecond-step jobs hundreds
        of times a minute.  It stays step-aligned: solo processes gate
        on local wall clock directly; multi-process worlds agree on the
        NEXT check step inside the current check's allgather (the
        proposal derives from each process's step-time EMA; the
        allgathered max is identical everywhere), so every process
        still enters the same collectives at the same steps.  The
        first check lands on a PREEMPT_CHECK_STEPS multiple — the only
        cadence every process can know before any agreement exists."""
        from edl_tpu.utils import constants as _c
        # participation is decided from ENV facts only (identical for
        # every process the launcher spawned): a process whose store
        # connect failed must still enter the allgather below with
        # seen=0, or the world's collectives mismatch and hang
        if (self.tenv is None or not self.tenv.pod_id
                or not self.tenv.cluster_stage):
            return
        multi = jax.process_count() > 1
        if multi:
            if self._preempt_next_check is None:
                if step % max(1, _c.PREEMPT_CHECK_STEPS):
                    return
            elif step != self._preempt_next_check:
                return
        else:
            now = time.monotonic()
            if now - self._preempt_last_check_t < _c.PREEMPT_CHECK_SECONDS:
                return
            self._preempt_last_check_t = now
        # only rank-0-in-pod reads the store (the _heartbeat convention
        # — N identical reads per pod would be pure traffic); the
        # allgather below fans a single sighting out to every process
        if self.store is not None and self.tenv.rank_in_pod == 0:
            if not self._preempt_seen:
                from edl_tpu.cluster import preempt
                try:
                    self._preempt_seen = preempt.get_preempt(
                        self.store, self.tenv.job_id,
                        self.tenv.cluster_stage) is not None
                except Exception:  # noqa: BLE001 — a blip is not a preempt
                    logger.exception("preempt flag read failed")
            if not self._reshard_seen and self._delta_ready():
                from edl_tpu.cluster import resize as resize_rec
                try:
                    flag = resize_rec.read_resize_flag(
                        self.store, self.tenv.job_id,
                        self.tenv.cluster_stage)
                    # ONLY a grow flag starts the cooperative pause: it
                    # runs a collective save, which is safe iff every
                    # old-world member is alive.  A shrink flag means a
                    # member is already gone — any op started now would
                    # hang (gloo never errors post-death ops); shrink
                    # delta rides the preemption flow or the in-flight
                    # crash conversion instead
                    self._reshard_seen = (flag is not None
                                          and flag.get("mode") == "grow")
                except Exception:  # noqa: BLE001 — a blip is not a resize
                    logger.exception("resize flag read failed")
        agreed = self._preempt_seen
        reshard = self._reshard_seen and self._delta_ready()
        if multi:
            # ONE allgather carries the two sightings and this process's
            # cadence proposal (steps ~= PREEMPT_CHECK_SECONDS of wall
            # time, from the step-time EMA); max()/any() of each part is
            # the same on every process, so sighting fan-out and
            # next-check agreement cost a single collective
            proposal = _c.PREEMPT_CHECK_STEPS
            if self._step_ema:
                proposal = round(
                    _c.PREEMPT_CHECK_SECONDS / max(self._step_ema, 1e-4))
            # pack sightings + proposal into one int32: proposal must
            # stay under the sightings' radix whatever the env says
            proposal = max(1, min(999_999, proposal))
            from edl_tpu.parallel.sharding import allgather_flag
            packed = allgather_flag(
                (int(self._preempt_seen) * 2 + int(reshard)) * 1_000_000
                + proposal)
            bits = packed // 1_000_000
            agreed = bool((bits // 2).any())
            reshard = bool((bits % 2).any())
            self._preempt_next_check = step + int((packed % 1_000_000).max())
        if not agreed:
            if reshard:
                # delta resize: the whole old world agreed to pause at
                # THIS step — commit a checkpoint here (the save is
                # collective, hence the agreement), snapshot the local
                # shards and unwind into the live reshard.  Preemption
                # wins when both are flagged: a preempted world must
                # still exit through its checkpoint.
                self._pause_for_reshard(state, meta, step)
            return
        logger.warning("preemption flagged: checkpointing at step %d",
                       step)
        if self.ckpt is not None:
            meta.step = step
            self._sync_data_checkpoint(meta)
            self.ckpt.save(step, state, meta, force=True)
            self.ckpt.wait()
            logger.info("preempt: checkpoint committed at step %d", step)
        # delta resize (controlled shrink): while the WHOLE old world is
        # still alive — the only moment collectives are guaranteed not
        # to hang — survivors snapshot their shards; after the commit
        # barrier below, the preempted pod's trainers exit as always and
        # the survivors unwind into a live reshard instead of exiting.
        # (Crash shrinks can't do this: gloo never errors an op STARTED
        # after a peer death, so stop-resume reaps those.)
        survive = None
        if self._delta_ready() and self.store is not None:
            try:
                from edl_tpu.cluster import preempt
                # per-pod check, NOT the single-slot flag's pod id:
                # with several pods preempted at once the slot names
                # only the last writer, and a departing pod that
                # misread itself as a survivor would never exit
                if not preempt.is_pod_preempted(
                        self.store, self.tenv.job_id,
                        self.tenv.cluster_stage, self.tenv.pod_id):
                    from edl_tpu.memstate import shards as ms_shards
                    shard_list, manifest = ms_shards.snapshot(state)
                    survive = {key: (manifest[key], _bytes_view(arr))
                               for key, arr in shard_list}
            except Exception:  # noqa: BLE001 — fall back to the exit
                logger.exception("preempt-survivor snapshot failed; "
                                 "taking the stop-resume exit")
                survive = None
        if jax.process_count() > 1:
            # every process's save must COMMIT before any process
            # leaves: the first abrupt exit trips the coordination
            # service's death-watch, which fatals the peers mid-save
            # (observed: the coordinator-hosting rank killed with exit
            # 1 while its shards were still writing)
            from edl_tpu.parallel.sharding import allgather_flag
            allgather_flag(1)
        if survive is not None:
            logger.warning("peer preempted: surviving in place — "
                           "unwinding into a live reshard")
            raise _LiveReshard(_ReshardPayload(mode="shrink",
                                               local=survive, step=step))
        # the workerlog must say WHY this pod died: its own per-pod
        # preempt record carries the eviction reason (sigterm /
        # descale / priority-yield / straggler-evict); a pod exiting on
        # a PEER's preemption agreement has no record of its own
        reason = "peer-preempt"
        if self.store is not None:
            try:
                from edl_tpu.cluster import preempt
                info = preempt.pod_preempt_info(
                    self.store, self.tenv.job_id, self.tenv.cluster_stage,
                    self.tenv.pod_id)
                if info is not None:
                    reason = info[1]
            except Exception as e:  # noqa: BLE001 — reason is best-effort
                logger.debug("preempt reason read failed: %s", e)
        logger.warning("preempt: exiting %d (reason=%s)",
                       _c.PREEMPT_EXIT_CODE, reason)
        # os._exit, NOT SystemExit: normal teardown runs jax's atexit
        # distributed shutdown, whose barrier hangs the coordinator-
        # hosting rank once a peer (exiting by the same agreement, a
        # beat earlier) has already disconnected — observed as a 2-min
        # DEADLINE_EXCEEDED fatal that overwrote the exit code.  The
        # whole world exits here together; there is nothing left to
        # coordinate, only buffers to flush.
        import logging as _logging
        import sys as _sys
        for h in _logging.getLogger().handlers:
            try:
                h.flush()
            # edl-lint: disable=wire-error — last-gasp flush before
            # os._exit; logging about a failed log flush cannot work
            except Exception:  # noqa: BLE001
                pass
        _sys.stdout.flush()
        _sys.stderr.flush()
        os._exit(_c.PREEMPT_EXIT_CODE)

    def _sync_data_checkpoint(self, meta: State) -> None:
        """Before every save, merge all processes' consumed data spans —
        the JSON sidecar is primary-host-only, but spans are marked by
        whichever host trained the records (data/elastic_input.py).
        Collective; save points are step-aligned across processes."""
        if jax.process_count() > 1:
            from edl_tpu.data.elastic_input import sync_checkpoint
            sync_checkpoint(meta.data_checkpoint)

    # -- delta resize: live reshard instead of stop-resume -------------------
    def _delta_ready(self) -> bool:
        """Can THIS trainer take the delta path?  Needs the knob, the
        launcher context, a checkpoint manager (the pause-save and the
        rollback target) and a capturable state skeleton."""
        from edl_tpu.utils import constants as _c
        return bool(_c.RESIZE_DELTA and self.ckpt is not None
                    and self.store is not None and self.tenv is not None
                    and self.tenv.pod_id and self.tenv.cluster_stage
                    and self._state_spec is not None)

    def _capture_state_spec(self, state) -> None:
        """Mesh-free skeleton of ``state`` (shape/dtype/PartitionSpec
        per array leaf) captured while the arrays are alive — a live
        reshard rebuilds the abstract restore target from it against
        the NEW mesh.  A state with non-NamedSharding array leaves
        can't be re-specced; _delta_ready then keeps this trainer on
        the stop-resume path."""
        from jax.sharding import NamedSharding

        def one(leaf):
            if not hasattr(leaf, "sharding"):
                return leaf  # static/non-array leaf: carried verbatim
            if not isinstance(leaf.sharding, NamedSharding):
                raise TypeError(f"non-NamedSharding leaf {type(leaf)}")
            return _LeafSpec(tuple(int(d) for d in leaf.shape),
                             leaf.dtype, leaf.sharding.spec)

        try:
            self._state_spec = jax.tree.map(one, state)
        except Exception:  # noqa: BLE001 — delta path disabled, not fatal
            logger.exception("state spec capture failed; delta resize "
                             "disabled for this trainer")
            self._state_spec = None

    def _respec(self):
        """The abstract restore target on the CURRENT (new) mesh."""
        mesh = self.mesh

        def one(leaf):
            if not isinstance(leaf, _LeafSpec):
                return leaf
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype,
                sharding=NamedSharding(mesh, leaf.spec))

        return jax.tree.map(one, self._state_spec,
                            is_leaf=lambda x: isinstance(x, _LeafSpec))

    def _pause_for_reshard(self, state, meta, step: int) -> None:
        """The cooperative (grow) pause: commit a world-wide checkpoint
        at the agreed step, host-snapshot this process's shards (the
        zero-wire local source for the reshard restore) and unwind.
        Raises :class:`_LiveReshard`; never returns."""
        logger.warning("delta resize flagged: pausing at step %d for a "
                       "live reshard", step)
        meta.step = step
        self._sync_data_checkpoint(meta)
        self.ckpt.save(step, state, meta, force=True)
        # wait() = storage durable + cache sets sealed + committed-step
        # record advanced: joiners and rolled-back peers restore THIS step
        self.ckpt.wait()
        from edl_tpu.memstate import shards as ms_shards
        shard_list, manifest = ms_shards.snapshot(state)
        local = {key: (manifest[key], _bytes_view(arr))
                 for key, arr in shard_list}
        if jax.process_count() > 1:
            # every process's save must COMMIT before any process tears
            # its backend down (same contract as the preemption exit):
            # the first leak_world would fail the stragglers' collective
            # save
            from edl_tpu.parallel.sharding import allgather_flag
            allgather_flag(1)
        raise _LiveReshard(_ReshardPayload(mode="grow", local=local,
                                           step=step))

    def _reshard_on_failure(self, exc: Exception,
                            detail: str) -> _ReshardPayload:
        """A peer pod's death fails survivors' collectives instantly —
        long before the membership change is visible.  When the delta
        path is on, wait (bounded) for the launcher's resize handshake
        and convert the crash into a rollback reshard; on timeout
        re-raise: the launcher handles the nonzero exit with the proven
        stop-resume fallback.  The caller already verified
        ``_delta_ready`` and released the failing frame's device
        arrays; ``detail`` is the formatted original traceback."""
        from edl_tpu.utils import constants as _c
        from edl_tpu.cluster import resize as resize_rec
        from edl_tpu.train import distributed as dist
        logger.warning("step failed; delta resize on — waiting up to "
                       "%.0fs for the resize handshake\n%s",
                       _c.RESIZE_RESHARD_TIMEOUT, detail)
        # tear the old backend down NOW, before any waiting: surviving
        # peers may be BLOCKED in a collective on THIS process (their
        # gloo reads wait on our sockets, not the dead pod's) — closing
        # our backend fails their reads within milliseconds, so the
        # whole old world converges on the handshake instead of hanging
        # until someone's timeout.  If the wait below times out and the
        # original error re-raises, the process exits anyway.  The mesh
        # must go first: its Device objects pin the old client (and so
        # its open sockets) through any clear_backends.
        self._step_fn = None
        self._flops_per_step = None
        self._eval_cache.clear()
        self.mesh = None
        dist.leak_world()
        deadline = time.monotonic() + _c.RESIZE_RESHARD_TIMEOUT
        old_stage = self.tenv.cluster_stage
        while time.monotonic() < deadline:
            try:
                if (resize_rec.read_go(self.store, self.tenv.job_id,
                                       old_stage) is not None
                        or resize_rec.read_resize_flag(
                            self.store, self.tenv.job_id, old_stage)
                        is not None):
                    # no save here: the dead pod's live-step shards are
                    # gone.  With the delta plane on, the reshard
                    # restore rolls forward to the freshest world-agreed
                    # chain step (≤ EDL_TPU_DELTA_EVERY steps lost);
                    # otherwise it rolls back to the committed step —
                    # the same data-loss window stop-resume has
                    return _ReshardPayload(mode="shrink")
            except Exception:  # noqa: BLE001 — store blip: keep polling
                logger.exception("resize handshake poll failed")
            time.sleep(0.5)
        raise exc

    def _live_reshard(self, payload: _ReshardPayload, meta):
        """Re-form the collective world in place and rebuild the train
        state, moving only the bytes this process does not already
        hold.  Any failure raises — the process exits nonzero and the
        launcher's reshard-deadline fallback stop-resumes."""
        from edl_tpu.cluster import resize as resize_rec
        from edl_tpu.cluster.cluster import Cluster
        from edl_tpu.memstate import reshard as ms_reshard
        from edl_tpu.memstate import restore as ms_restore
        from edl_tpu.train import distributed as dist
        from edl_tpu.utils import constants as _c

        t0 = time.monotonic()
        t_detect = time.time()
        builds_at_detect = _BUILDS.totals()
        old_stage = self.tenv.cluster_stage
        old_world = self.tenv.world_size
        # drop every executable/compiled reference into the old backend
        # and abandon the old world BEFORE any waiting (idempotent — the
        # crash path already did it): peers may be blocked on our gloo
        # sockets, and the pause path has nothing left to compute.  The
        # mesh's Device objects pin the old client, so it goes first
        self._step_fn = None
        self._flops_per_step = None
        self._eval_cache.clear()
        self.mesh = None
        dist.leak_world()

        # 1. the definitive target stage (written post-barrier by the
        # launcher) + its cluster record
        deadline = time.monotonic() + _c.RESIZE_RESHARD_TIMEOUT
        go = None
        while time.monotonic() < deadline:
            go = resize_rec.read_go(self.store, self.tenv.job_id, old_stage)
            if go is not None:
                break
            time.sleep(0.2)
        if go is None:
            raise RuntimeError(
                f"no reshard go record for stage {old_stage[:8]} within "
                f"{_c.RESIZE_RESHARD_TIMEOUT:.0f}s")
        cluster = None
        while time.monotonic() < deadline:
            cluster = Cluster.load_from_store(self.store, self.tenv.job_id)
            if cluster is not None and cluster.stage == go["new_stage"]:
                break
            # a resize superseding THIS resize re-points the go record
            go = resize_rec.read_go(self.store, self.tenv.job_id,
                                    old_stage) or go
            time.sleep(0.2)
        if cluster is None or cluster.stage != go["new_stage"]:
            raise RuntimeError(
                f"cluster record never reached go stage "
                f"{go['new_stage'][:8]}")

        # 2. re-form the world in this process (leaks the old one —
        # see train/distributed.py's teardown contract), rebuild mesh
        with obs_trace.get_tracer().span("train/reshard",
                                         mode=payload.mode), \
                _BUILDS.setup("train", key="reshard"):
            # the OLD checkpoint manager is abandoned, never closed:
            # its close path can barrier against a world that no longer
            # exists (a dead peer on shrink).  Kept referenced so GC
            # can't run its destructor either; its tee is local-only
            # and safe to stop.
            if self.ckpt is not None:
                _ABANDONED_CKPTS.append(self.ckpt.abandon())
            dist.reform_world(self.tenv, self.store, cluster)
            # construct the NEW manager first thing in the new world:
            # its construction sync pairs with the construction sync of
            # freshly spawned joiner trainers, and Orbax's operation id
            # resets so survivor and joiner signal keys agree
            # (checkpoint.reset_multihost_counters)
            from edl_tpu.train.checkpoint import reset_multihost_counters
            reset_multihost_counters()
            self.ckpt = self._build_ckpt()
            self.mesh = build_mesh(self.cfg.mesh_spec, None)
            abstract = self._respec()

            # 3. rebuild state: local snapshot first (zero wire), own
            # pod's cache over loopback next, peers/replicas for the
            # shards whose owner changed — the delta.  When the world
            # agrees a streamed delta chain reaches PAST the committed
            # step (a failure shrink: the base + survivors' chains are
            # fresher than any checkpoint), overlay it first.  The
            # collective order here (ckpt construction sync, then the
            # target agreement, then the restore, then the all-ok vote)
            # mirrors _cache_first_restore exactly, because survivors
            # and freshly spawned joiners run these collectives against
            # each other.
            expect = self.ckpt.latest_step()
            t_restore = time.time()
            delta_step = self._agree_delta_target(expect)
            res = None
            if delta_step is not None:
                try:
                    res = ms_restore.try_restore(
                        self.store, self.tenv.job_id, abstract,
                        expect_step=expect, local=payload.local,
                        prefer_pod=self.tenv.pod_id,
                        delta_step=delta_step)
                except Exception:  # noqa: BLE001 — demote to base
                    logger.exception("reshard delta-chain restore errored")
                if not self._agree_flag(res is not None):
                    res = None
            if res is None:
                try:
                    res = ms_restore.try_restore(
                        self.store, self.tenv.job_id, abstract,
                        expect_step=expect, local=payload.local,
                        prefer_pod=self.tenv.pod_id)
                except Exception:  # noqa: BLE001 — storage fallback below
                    logger.exception("reshard cache restore errored")
            if res is not None:
                state, meta_json, info = res
                meta = State().from_json(meta_json)
                source = "delta"
                ms_reshard.BYTES_KEPT.inc(info.get("local_bytes", 0))
                ms_reshard.BYTES_MOVED.inc(info.get("wire_bytes", 0))
                ms_reshard.SHARDS_TOTAL.inc(info.get("shards", 0))
                ms_reshard.SHARDS_MOVED.inc(
                    info.get("shards", 0) - info.get("local_shards", 0))
                logger.info(
                    "reshard restore: step %d, %.1f MB local / %.1f MB "
                    "moved", info.get("step", -1),
                    info.get("local_bytes", 0) / 1e6,
                    info.get("wire_bytes", 0) / 1e6)
            else:
                # the world stays alive either way: a cache miss only
                # demotes the restore to storage, not the resize to
                # stop-resume
                restored = self.ckpt.restore(abstract)
                if restored is None:
                    raise RuntimeError("no checkpoint to reshard from")
                state, saved_meta = restored
                meta = saved_meta if saved_meta is not None else meta
                source = "storage"
            if os.environ.get("EDL_TPU_MEMSTATE_VERIFY") == "1" \
                    and source == "delta" and delta_step is None:
                # only comparable when no chain overlay ran: a chain
                # restore lands past the stored step by construction
                stored = self.ckpt.restore(abstract)
                assert stored is not None
                ms_restore.assert_bit_identical(state, stored[0])
                logger.info("reshard restore verified bit-identical to "
                            "storage (step %s)", expect)

        # 4. bookkeeping: adjust hooks, recovery instrumentation,
        # cadence state (joiners start fresh — agreed-step counters
        # must not diverge from theirs), done record for the launcher
        new_world = self.tenv.world_size
        if old_world != new_world:
            logger.info("world size %d -> %d (live); running adjust "
                        "functions", old_world, new_world)
            self.adjust.run(old_world, new_world, meta)
            # adjust hooks only see meta; the LR rescale touches the
            # optimizer state, so it is applied here directly
            state = self._world_lr_rescale(state, old_world, new_world)
        self._reshard_seen = False
        # a preemption sighting belongs to the OLD stage: the departed
        # pod is gone; the new stage must not re-trigger on it
        self._preempt_seen = False
        self._preempt_next_check = None
        self._last_step_t = None
        # a live reshard's record runs from the detection: so does what
        # the program-build ledger says was built for it
        self._t_restored = t_detect
        self._builds_at_restored = builds_at_detect
        self._restore_source = source
        ms_restore.RESTORE_SECONDS.labels(source=source).observe(
            time.monotonic() - t0)
        if self.tenv.rank_in_pod == 0:
            try:
                resize_rec.write_done(
                    self.store, self.tenv.job_id, cluster.stage,
                    self.tenv.pod_id,
                    {"mode": payload.mode, "source": source,
                     "seconds": round(time.monotonic() - t0, 3)})
            except Exception:  # noqa: BLE001 — the launcher's deadline
                logger.exception("reshard done record write failed")
        self._capture_state_spec(state)
        if self._delta_rep is not None and delta_step is None:
            # restored at the committed step: re-anchor the (freshly
            # rebuilt) replicator's chain there so delta streaming
            # resumes immediately.  After a chain-overlay restore the
            # landed step has no full base — streaming waits for the
            # next save's rebase, and the existing chains stay servable
            # until that save's commit compacts them away.
            self._delta_rep.rebase(int(state.step), state)
        logger.info("live reshard complete: stage %s, world %d, %.2fs "
                    "(source=%s, step %d)", cluster.stage[:8], new_world,
                    time.monotonic() - t0, source, int(state.step))
        return state, meta

    # -- eval ----------------------------------------------------------------
    def make_eval_step(self, metric_fn):
        """Masked-sum eval step for ``metric_fn(params, extra, batch) ->
        {name: (B,) per-example values}``, jitted once per metric_fn and
        cached (a fresh jit per epoch would recompile the eval graph
        every time)."""
        key = id(metric_fn)
        cached = self._eval_cache.get(key)
        if cached is None:
            def step(params, extra, batch, mask):
                vals = metric_fn(params, extra, batch)
                return ({k: (v * mask).sum() for k, v in vals.items()},
                        mask.sum())
            cached = (metric_fn, jax.jit(step))
            self._eval_cache[key] = cached
            while len(self._eval_cache) > 8:  # LRU-ish bound (advisor r2)
                self._eval_cache.popitem(last=False)
        else:
            self._eval_cache.move_to_end(key)
        return cached[1]

    def evaluate(self, state: TrainState, batches: Iterable[Any],
                 metric_fn) -> dict[str, float]:
        """Sample-weighted means of per-example metrics — the per-epoch
        test pass of the reference (train_with_fleet.py:642-658).

        ``metric_fn(params, extra, batch) -> {name: (B,) array}`` — one
        value per example, so ragged final batches can be zero-padded to
        the mesh's batch divisor and masked out exactly.

        Multi-host: a per-batch has-next agreement (one tiny allgather)
        keeps every process stepping together even when hosts yield
        DIFFERENT batch counts — a host that runs out feeds a zero
        batch with a zero mask until all are done.  (Round-2 verdict
        weak #4: the old contract was a docstring; an extra batch on one
        host hung the job.)"""
        jitted = self.make_eval_step(metric_fn)
        div = batch_divisor(self.mesh)
        totals: dict[str, float] = {}
        count = 0.0
        it = iter(batches)
        multi = jax.process_count() > 1
        template = None
        while True:
            batch = next(it, None)
            if multi:
                from edl_tpu.parallel.sharding import allgather_flag
                flags = allgather_flag(int(batch is not None))
                if not flags.any():
                    break
                if batch is None:
                    if template is None:
                        raise RuntimeError(
                            "evaluate: this host ran out of eval batches "
                            "before yielding any — it cannot shape filler "
                            "batches for the remaining collective steps; "
                            "give every host at least one batch")
                    batch = jax.tree.map(
                        lambda x: np.zeros_like(np.asarray(x)), template)
                    n = 0  # all rows are filler
                else:
                    template = batch
                    n = len(next(iter(jax.tree.leaves(batch))))
            elif batch is None:
                break
            else:
                n = len(next(iter(jax.tree.leaves(batch))))
            rows = len(next(iter(jax.tree.leaves(batch))))
            size = rows + ((-rows) % div)
            if rows < size:
                batch = jax.tree.map(
                    lambda x: np.concatenate(
                        [np.asarray(x),
                         np.zeros((size - rows,) + np.asarray(x).shape[1:],
                                  np.asarray(x).dtype)]), batch)
            mask = np.concatenate([np.ones(n, np.float32),
                                   np.zeros(size - n, np.float32)])
            g = shard_host_batch({"batch": batch, "mask": mask},
                                 self.mesh, self.rules)
            sums, m = jitted(state.params, state.extra, g["batch"], g["mask"])
            for k, v in sums.items():
                totals[k] = totals.get(k, 0.0) + float(v)
            count += float(m)
        return {k: v / max(1.0, count) for k, v in totals.items()}

    # -- train-status reporting ---------------------------------------------
    def _report(self, status: TrainStatus) -> None:
        if self.store is None or self.tenv is None or not self.tenv.pod_id:
            return
        try:
            save_train_status(self.store, self.tenv.job_id, self.tenv.pod_id,
                              status)
        except Exception:  # noqa: BLE001 — reporting is best-effort
            logger.exception("train-status report failed")


def _bytes_view(arr) -> memoryview | bytes:
    """Zero-copy byte view of a host shard for the reshard's local
    source (len() = byte length, np.frombuffer-compatible); copies only
    when the dtype's buffer format can't be cast (ml_dtypes extras on
    some numpy builds)."""
    a = np.ascontiguousarray(arr).reshape(-1)
    try:
        return memoryview(a).cast("B")
    except (TypeError, ValueError):
        return a.tobytes()


def _map_params_like(opt_state, params, fn):
    """Apply ``fn`` to every subtree of ``opt_state`` that mirrors the
    params pytree (same structure, same leaf shapes) — optax momenta
    (e.g. ScaleByAdamState.mu/nu) — so optimizer state is sharded like
    its parameters.  Scalar bookkeeping (step counts) is left alone."""
    pdef = jax.tree.structure(params)
    pshapes = [getattr(l, "shape", None) for l in jax.tree.leaves(params)]

    def is_params_like(x):
        try:
            if jax.tree.structure(x) != pdef:
                return False
            return [getattr(l, "shape", None)
                    for l in jax.tree.leaves(x)] == pshapes
        # edl-lint: disable=wire-error — structural probe: False is
        # the answer for "not params-shaped", not a swallowed error
        except Exception:  # noqa: BLE001 — non-pytree nodes
            return False

    return jax.tree.map(lambda x: fn(x) if is_params_like(x) else x,
                        opt_state, is_leaf=is_params_like)


def _last_world(meta: State) -> int:
    """World size of the most recent recorded epoch."""
    if not meta.epochs:
        return 0
    return max(meta.epochs, key=lambda e: e.epoch_no).world_size


def _merge_logical(base, override):
    """Overlay user-specified logical axes onto a replicate-all tree."""
    if override is None:
        return base
    def pick(b, o):
        return b if o is None else o
    return jax.tree.map(pick, base, override,
                        is_leaf=lambda x: x is None or (
                            isinstance(x, tuple) and all(
                                a is None or isinstance(a, str) for a in x)))
