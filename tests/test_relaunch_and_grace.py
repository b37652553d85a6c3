"""Regression tests for rerun-after-failure cleanup and fail-grace.

Covers the findings: stale unleased pod_status records disabling
scale-out on a job_id rerun, and collateral trainer crashes failing the
job before the membership change arrives.
"""

import time

from edl_tpu.cluster import paths
from edl_tpu.cluster.status import (
    Status, load_job_status, load_pods_status, save_job_status, save_pod_status,
)
from edl_tpu.collective.launch import clear_stale_job_tables
from edl_tpu.collective.resource import load_resource_pods, register_pod
from edl_tpu.utils import constants
from tests.test_cluster_model import make_pod
from tests.test_elastic_control import wait_for

JOB = "job-rerun"


def test_clear_stale_tables_on_dead_job(memkv):
    # dead run left unleased records behind
    save_pod_status(memkv, JOB, "deadpod", Status.SUCCEED)
    save_job_status(memkv, JOB, Status.FAILED)
    memkv.put(paths.key(JOB, constants.ETCD_CLUSTER, "cluster"), b"{}")
    memkv.put(paths.key(JOB, constants.ETCD_STATE, "state"), b"keepme")

    clear_stale_job_tables(memkv, JOB)
    assert load_pods_status(memkv, JOB) == {}
    assert load_job_status(memkv, JOB) is None
    # state (data checkpoint) survives for resume
    assert memkv.get(paths.key(JOB, constants.ETCD_STATE, "state")).value == b"keepme"


def test_clear_skipped_while_job_live(memkv):
    # a provisionally-FAILED flag with live pods = elastically recovering
    # run; a relaunching pod must not wipe its records
    pod = make_pod()
    reg = register_pod(memkv, JOB, pod, ttl=5.0)
    assert wait_for(lambda: pod.pod_id in load_resource_pods(memkv, JOB))
    save_pod_status(memkv, JOB, pod.pod_id, Status.RUNNING)
    save_job_status(memkv, JOB, Status.FAILED)

    clear_stale_job_tables(memkv, JOB)  # we are a scale-out joiner: no-op
    assert load_pods_status(memkv, JOB) == {pod.pod_id: Status.RUNNING}
    assert load_job_status(memkv, JOB) == Status.FAILED
    reg.stop()


def test_clear_noop_on_fresh_job(memkv):
    # no FAILED flag → never clean (simultaneous fresh launch is safe)
    save_pod_status(memkv, JOB, "earlybird", Status.INITIAL)
    clear_stale_job_tables(memkv, JOB)
    assert load_pods_status(memkv, JOB) == {"earlybird": Status.INITIAL}


def test_clear_claimed_once(memkv):
    save_pod_status(memkv, JOB, "deadpod", Status.SUCCEED)
    save_job_status(memkv, JOB, Status.FAILED)
    clear_stale_job_tables(memkv, JOB)        # claims + cleans
    save_pod_status(memkv, JOB, "newpod", Status.INITIAL)
    clear_stale_job_tables(memkv, JOB)        # no flag → no-op
    assert load_pods_status(memkv, JOB) == {"newpod": Status.INITIAL}


class _FakeWatcher:
    def __init__(self):
        self.changed = False

    def stop(self):
        pass


def test_supervise_grace_turns_peer_crash_into_resize(monkeypatch):
    """A local FAILED followed by a membership change inside the grace
    window must return None (resize), not FAILED."""
    from edl_tpu.collective import launcher as launcher_mod

    monkeypatch.setattr(launcher_mod.constants, "FAIL_GRACE", 0.3)
    lch = launcher_mod.Launcher.__new__(launcher_mod.Launcher)
    lch._procs = []
    lch._period = 0.02
    lch._ttl = 0.2
    import threading as _t
    lch._preempt_event = _t.Event()
    lch._preempt_stage = None
    lch._preempt_deadline = None

    class _Alive:
        is_stopped = False
    lch._resource_register = _Alive()
    lch._elector = _Alive()

    monkeypatch.setattr(launcher_mod.train_process, "watch_procs",
                        lambda procs: Status.FAILED)
    watcher = _FakeWatcher()

    # membership change arrives 0.1 s after the crash
    def flip():
        time.sleep(0.1)
        watcher.changed = True
    import threading
    threading.Thread(target=flip, daemon=True).start()
    assert lch._supervise(watcher, None) is None

    # no membership change → grace expires → FAILED
    watcher2 = _FakeWatcher()
    start = time.monotonic()
    assert lch._supervise(watcher2, None) == Status.FAILED
    assert time.monotonic() - start >= lch._fail_grace()


def test_second_launcher_on_a_hosts_chips_is_refused(coord_server):
    """Trainers sharing one TPU host's chips cannot form a correct
    world on libtpu (ROADMAP S9c), and the resize a second pod triggers
    would take the running trainer down: a launcher with ``--devices``
    refuses to join a job that already holds chips on this host."""
    from edl_tpu.cluster.pod import Pod
    from edl_tpu.collective import launch
    from edl_tpu.coord.client import CoordClient
    from edl_tpu.utils.network import local_ip

    ep = f"127.0.0.1:{coord_server.port}"
    client = CoordClient(ep)
    reg = register_pod(client, JOB, Pod(addr=local_ip(), device_ids=[0, 1]))
    try:
        rc = launch.run(["--job_id", JOB, "--coord_endpoints", ep,
                         "--devices", "2,3", "train.py"])
        assert rc == 1
        assert len(load_resource_pods(client, JOB)) == 1    # never joined
    finally:
        reg.stop()
        client.close()
