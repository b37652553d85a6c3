"""CLI entry: ``python -m edl_tpu.collective.launch`` — run on every host.

Reference: python/edl/collective/launch.py (the ``edlrun`` console
script).  Parses args + env into a JobEnv, skips the job if it already
SUCCEEDed (launch.py:44-47), builds this host's Pod, and runs the
Launcher until the job finishes or this pod fails.

Example::

    python -m edl_tpu.collective.launch \
        --job_id imagenet-rn50 --coord_endpoints 10.0.0.2:2379 \
        --nodes_range 2:8 --nproc_per_node 1 \
        train.py --epochs 90 --batch_size 256
"""

from __future__ import annotations

import argparse
import sys

from edl_tpu.cluster.env import JobEnv
from edl_tpu.cluster.pod import Pod
from edl_tpu.cluster.status import Status, load_job_status
from edl_tpu.collective.launcher import Launcher
from edl_tpu.coord.client import connect_wait
from edl_tpu.utils.logger import configure, get_logger
from edl_tpu.utils.network import find_free_ports, local_ip

logger = get_logger(__name__)


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "edl_tpu.collective.launch",
        description="Elastic TPU training launcher (one per host)")
    p.add_argument("--job_id", type=str, default=None)
    p.add_argument("--coord_endpoints", type=str, default=None,
                   help="comma-separated coordination-store endpoints")
    p.add_argument("--nodes_range", type=str, default=None, help="min:max hosts")
    p.add_argument("--nproc_per_node", type=int, default=None)
    p.add_argument("--devices", type=str, default=None,
                   help="comma-separated local device ids (default: all)")
    p.add_argument("--checkpoint_dir", type=str, default=None)
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("--log_level", type=str, default=None)
    p.add_argument("training_script", type=str)
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p


def clear_stale_job_tables(store, job_id: str) -> None:
    """Purge leftover records when relaunching a previously FAILED job.

    Pod/train statuses and the cluster record are written without
    leases, so a FAILED run leaves them behind; an unleased
    ``pod_status=SUCCEED`` from a dead run would permanently disable
    scale-out (the generator's any_succeeded rule).

    Race safety: only runs when a FAILED job flag exists (a fresh job
    never cleans, so a normal simultaneous multi-host launch can't wipe
    peers' records), claims cleanup by being the one launcher whose
    ``delete`` of the flag returns nonzero, and never touches leased
    tables (``resource``, ``rank``) — stale leased keys expire on their
    own, and deleting live ones would disturb a running election.
    ``state`` is kept too: it carries the data checkpoint used for
    resume (reference state.py:186-200).
    """
    from edl_tpu.cluster import paths
    from edl_tpu.collective.resource import load_resource_pods
    from edl_tpu.utils import constants

    if load_job_status(store, job_id) != Status.FAILED:
        return
    if load_resource_pods(store, job_id):
        return  # live (elastically recovering) run; its leader will re-flag
    if not store.delete(paths.key(job_id, constants.ETCD_JOB_STATUS, "job")):
        return  # another relaunching pod claimed the cleanup
    for table in (constants.ETCD_POD_STATUS, constants.ETCD_TRAIN_STATUS,
                  constants.ETCD_CLUSTER, constants.ETCD_READER,
                  constants.ETCD_DIST_READER, constants.ETCD_SCALE):
        # ETCD_SCALE: a stale desired-nodes record from the previous
        # incarnation would permanently cap the relaunched job's
        # cluster below its nodes_range (a live controller re-writes it)
        store.delete_prefix(paths.table_prefix(job_id, table))


def run(argv: list[str] | None = None) -> int:
    args = build_arg_parser().parse_args(argv)
    job_env = JobEnv(args)
    configure(job_env.log_level)
    from edl_tpu import obs
    obs.install_from_env("launcher")  # /metrics + JSONL trace, env-gated

    # tolerate the coordination pod booting (or restarting) after us:
    # backoff-retried connect instead of one shot
    store = connect_wait(job_env.coord_endpoints)
    if load_job_status(store, job_env.job_id) == Status.SUCCEED:
        logger.info("job %s already SUCCEED; nothing to do", job_env.job_id)
        return 0
    clear_stale_job_tables(store, job_env.job_id)

    pod = Pod(addr=local_ip(), device_ids=job_env.device_ids)
    if pod.device_ids:
        # joining would put two trainers on one host's chips, which
        # libtpu cannot yet form into a correct world — and the resize
        # would take the running pod's trainer down with ours
        # (cluster/env.tpu_visibility_vars, ROADMAP S9c)
        from edl_tpu.collective.resource import load_resource_pods
        held = [p for p in load_resource_pods(store, job_env.job_id).values()
                if p.addr == pod.addr and p.device_ids]
        if held:
            logger.error(
                "refusing to join job %s with --devices %s: pod %s on this "
                "host already holds chips %s, and trainers sharing one "
                "host's chips cannot form one world (ROADMAP S9c)",
                job_env.job_id, pod.device_ids, held[0].pod_id[:8],
                held[0].device_ids)
            return 1
    pod.make_trainers(job_env.nproc_per_node,
                      find_free_ports(job_env.nproc_per_node))
    logger.info("pod %s on %s launching job %s", pod.pod_id, pod.addr, job_env.job_id)

    launcher = Launcher(job_env, pod, store, args.training_script,
                        args.script_args)
    # TPU pods are preempted with SIGTERM + grace: trap it so trainers
    # checkpoint at an agreed step and this pod departs DESCALED while
    # peers resize — instead of looking like a crash and losing up to a
    # full checkpoint interval (cluster/preempt.py).  Handler is
    # signal-safe: it only sets an event the supervisor loop acts on.
    import signal

    try:
        signal.signal(signal.SIGTERM,
                      lambda *_: launcher.request_preempt())
    except ValueError:  # pragma: no cover - non-main-thread embedding
        logger.warning("not main thread; SIGTERM preemption grace disabled")
    final = launcher.launch()
    logger.info("pod %s finished with %s", pod.pod_id, final.value)
    # DESCALED = scaled out by the controller: a clean departure (the
    # job continues on the remaining pods), not a failure
    return 0 if final in (Status.SUCCEED, Status.DESCALED) else 1


def main():  # pragma: no cover - thin wrapper
    sys.exit(run())


if __name__ == "__main__":
    main()
