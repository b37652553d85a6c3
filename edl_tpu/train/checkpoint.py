"""Checkpointing: Orbax array state + JSON resume sidecar.

Replaces ``fleet.save_check_point/load_check_point`` + HDFS
(train_with_fleet.py:426-434, :562-570; doc/fault_tolerance.md:1-63).
Guarantees the reference documented — write-temp-then-rename atomicity,
versioned step directories, keep-N garbage collection — come from
Orbax's CheckpointManager; saving is async so the train loop never
blocks on storage (the reference blocked every epoch).

Every pod calls ``save``; Orbax's multiprocess protocol writes each
array shard once from its owning host (vs the reference where only
rank 0 saved — fine for replicated DP, wrong for sharded states).
"""

from __future__ import annotations

import json
import os
import resource
import time
from typing import Any

import orbax.checkpoint as ocp

from edl_tpu.cluster.state import State
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.utils.logger import get_logger

logger = get_logger(__name__)

# saves are async: _save_seconds is the synchronous (blocking-the-step)
# part of save(); _wait_seconds is the commit drain (epoch boundaries,
# preemption); restore is fully synchronous
_SAVE_SECONDS = obs_metrics.histogram(
    "edl_checkpoint_save_seconds",
    "Synchronous portion of a checkpoint save (seconds)")
_WAIT_SECONDS = obs_metrics.histogram(
    "edl_checkpoint_wait_seconds",
    "Async checkpoint commit drain (seconds)")
_RESTORE_SECONDS = obs_metrics.histogram(
    "edl_checkpoint_restore_seconds", "Checkpoint restore (seconds)")
_SAVES_TOTAL = obs_metrics.counter(
    "edl_checkpoint_saves_total", "Checkpoint saves accepted")
# the memstate tee's synchronous D2H snapshot is metered apart from
# _SAVE_SECONDS so enabling the cache never skews the Orbax save metric
_TEE_STAGE_SECONDS = obs_metrics.histogram(
    "edl_memstate_stage_seconds",
    "Synchronous memstate tee snapshot during save() (seconds)")
_RESTORES_TOTAL = obs_metrics.counter(
    "edl_checkpoint_restores_total", "Checkpoint restores completed")

# TensorStore's OCDBT driver batches whatever writes are pending into ONE
# data file, up to a 2 GiB default: the 12 x 768 flagship's 1.9 GB of
# state landed in files of 90-480 MB, and a trainer held to a smaller
# RLIMIT_FSIZE died with EFBIG inside its first commit (PR 21, the chip
# check's machine).  With a target T Orbax also chunks every array to
# <= T, and a file closes once it holds T: none reaches 2 T.
_DATA_FILE_TARGET = 32 << 20


def _data_file_target() -> int:
    """OCDBT target data-file size: 32 MiB, or a third of this process's
    file-size limit where that is lower."""
    soft, _ = resource.getrlimit(resource.RLIMIT_FSIZE)
    if soft == resource.RLIM_INFINITY:
        return _DATA_FILE_TARGET
    return min(_DATA_FILE_TARGET, soft // 3)


class CheckpointManager:
    def __init__(self, directory: str, max_to_keep: int = 3,
                 async_save: bool = True, save_interval_steps: int = 0,
                 tee=None):
        # ``tee`` (memstate.StateCacheTee): every committed save is
        # mirrored into the pod's in-RAM peer cache — staged at save()
        # (the D2H snapshot can't outlive the donated buffers), sealed
        # at wait() (only a storage-durable step may become servable).
        # Strictly best-effort: a tee failure costs a cache miss, never
        # the checkpoint.
        self._tee = tee
        if "://" in directory:  # object store (gs://...): Orbax/epath I/O
            self._dir = directory
        else:
            self._dir = os.path.abspath(directory)
            os.makedirs(self._dir, exist_ok=True)
        opts = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            enable_async_checkpointing=async_save,
            save_interval_steps=max(1, save_interval_steps) if save_interval_steps else 1,
            # Elastic stop-resume can SIGKILL a trainer mid-async-save;
            # without this the stale <step>.orbax-checkpoint-tmp poisons
            # the restarted run's save of the same step (FileExistsError
            # on primary, rename ENOENT on peers).
            cleanup_tmp_directories=True,
        )
        self._mngr = ocp.CheckpointManager(self._dir, options=opts)

    # -- save ---------------------------------------------------------------
    def save(self, step: int, state: Any, meta: State | None = None,
             force: bool = False) -> bool:
        # PyTreeSave/PyTreeRestore are what StandardSave/StandardRestore
        # wrap; only the former take the data-file target
        args = {"state": ocp.args.PyTreeSave(
            state, ocdbt_target_data_file_size=_data_file_target())}
        if meta is not None:
            args["meta"] = ocp.args.JsonSave(meta.to_dict())
        t0 = time.perf_counter()
        saved = self._mngr.save(step, args=ocp.args.Composite(**args), force=force)
        if saved:
            _SAVE_SECONDS.observe(time.perf_counter() - t0)
        if saved and self._tee is not None:
            t1 = time.perf_counter()
            try:
                self._tee.stage(step, state, meta)
            except Exception:  # noqa: BLE001 — cache is best-effort
                logger.exception("memstate tee stage failed (step %d)", step)
            _TEE_STAGE_SECONDS.observe(time.perf_counter() - t1)
        if saved:
            _SAVES_TOTAL.inc()
            logger.info("checkpoint step %d queued to %s", step, self._dir)
        return saved

    # -- restore ------------------------------------------------------------
    def latest_step(self) -> int | None:
        return self._mngr.latest_step()

    def restore(self, abstract_state: Any,
                step: int | None = None) -> tuple[Any, State | None] | None:
        """Restore (state, meta) at ``step`` (default latest); None if no
        checkpoint exists — the resume-or-cold-start switch
        (train_with_fleet.py:426-434)."""
        step = self.latest_step() if step is None else step
        if step is None:
            return None
        t0 = time.perf_counter()
        state_args = ocp.args.PyTreeRestore(
            item=abstract_state,
            restore_args=ocp.checkpoint_utils.construct_restore_args(
                abstract_state))
        if self._has_item(step, "meta"):
            restored = self._mngr.restore(
                step, args=ocp.args.Composite(
                    state=state_args, meta=ocp.args.JsonRestore()))
        else:
            # checkpoint written without a State sidecar (e.g. a served
            # model exported by save(step, state) alone).  Checked
            # explicitly instead of catching KeyError around the whole
            # restore: a KeyError from the state restore itself (pytree
            # mismatch) must surface, not trigger a second restore.
            restored = self._mngr.restore(
                step, args=ocp.args.Composite(state=state_args))
        meta = None
        if restored.get("meta") is not None:
            meta = State().from_dict(restored["meta"])
        _RESTORE_SECONDS.observe(time.perf_counter() - t0)
        _RESTORES_TOTAL.inc()
        logger.info("restored checkpoint step %d from %s", step, self._dir)
        return restored["state"], meta

    def save_meta(self, step: int, meta: State) -> bool:
        """Atomically rewrite just the JSON sidecar of an already-committed
        checkpoint — for post-save hooks (eval records) that mutate the
        State after the epoch's array save.  Orders of magnitude cheaper
        than re-saving the arrays, and leaves the committed checkpoint
        restorable at every instant (write-tmp-then-rename)."""
        import jax
        if jax.process_index() != 0:
            return False  # JSON items are written by the primary host only
        self._mngr.wait_until_finished()  # ensure the step is committed
        d = self._mngr.directory / str(step) / "meta"
        if not d.exists():
            return False
        body = json.dumps(meta.to_dict())
        if "://" in str(d):
            # object store (gs://...): a single-object write is atomic;
            # there is no cross-object rename to lean on
            (d / "metadata").write_text(body)
            self._tee_meta(step, meta)
            return True
        path = os.path.join(str(d), "metadata")
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(body)
        os.replace(tmp, path)
        self._tee_meta(step, meta)
        return True

    def _tee_meta(self, step: int, meta: State) -> None:
        """Mirror a sidecar patch into the cache so a peer restore sees
        the same post-hook State the storage sidecar holds."""
        if self._tee is None:
            return
        try:
            self._tee.update_meta(step, meta)
        except Exception:  # noqa: BLE001 — cache is best-effort
            logger.exception("memstate tee meta update failed (step %d)", step)

    def _has_item(self, step: int, name: str) -> bool:
        """Whether the checkpoint at ``step`` contains item ``name``."""
        try:
            return (self._mngr.directory / str(step) / name).exists()
        # edl-lint: disable=wire-error — layout probe whose fallback
        # return IS the handling (the composite restore re-validates)
        except Exception:  # noqa: BLE001 — layout probe is best-effort
            return True  # assume present; the composite restore will say

    def wait(self) -> None:
        t0 = time.perf_counter()
        self._mngr.wait_until_finished()
        if self._tee is not None:
            # storage is durable up to every queued step: staged cache
            # sets may now seal and advertise themselves as restorable
            self._tee.mark_committed()
        _WAIT_SECONDS.observe(time.perf_counter() - t0)

    def close(self) -> None:
        self._mngr.wait_until_finished()
        if self._tee is not None:
            self._tee.mark_committed()
            self._tee.close()
        self._mngr.close()

    def abandon(self):
        """Detach for a live reshard: stop the (local-only, safe) tee
        and hand back the raw Orbax manager WITHOUT closing it — in a
        multiprocess world close/wait can barrier against a collective
        world that no longer exists (a dead peer mid-shrink).  The
        caller must keep the returned object referenced so GC never
        runs its teardown either; a fresh CheckpointManager over the
        same directory takes over (train/trainer._live_reshard)."""
        if self._tee is not None:
            try:
                self._tee.close()
            except Exception:  # noqa: BLE001 — cache is best-effort
                logger.exception("tee close during abandon failed")
            self._tee = None
        mngr, self._mngr = self._mngr, None
        return mngr


def reset_multihost_counters() -> None:
    """Align Orbax's process-local operation id across a world whose
    members have divergent histories.

    Orbax 0.11 names its multihost barriers statically, but the
    directory-creation signals of an async save travel through the
    coordination service's key-value store under
    ``OperationIdGenerator``'s process-wide id (one tick per save).
    Ids normally advance in lockstep on every process; after a LIVE
    reshard the survivors have ticked theirs many times while a freshly
    spawned joiner starts at zero — the joiner would wait for a signal
    key the survivors never write.  Survivors therefore put the
    generator back to its import-time state before constructing their
    post-reshard manager, restoring lockstep with the joiners by
    construction."""
    import itertools

    from orbax.checkpoint._src.futures.synchronization import (
        OperationIdGenerator,
    )
    OperationIdGenerator._operation_id_counter = itertools.count()
    OperationIdGenerator._operation_id = next(
        OperationIdGenerator._operation_id_counter)
