"""End-to-end launcher integration: real launcher subprocesses against a
real coordination server, inert trainers, exit-code fault injection,
and a live elastic resize.

Port of the reference's multi-process no-GPU strategy
(test_launch.sh:50-61, SURVEY.md §4): pods are processes, the cluster
is coordination-store state, trainers are inert.
"""

import os
import subprocess
import sys
import time

import pytest

from edl_tpu.cluster.status import Status, load_job_status
from edl_tpu.coord.client import CoordClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEMO = os.path.join(REPO, "tests", "helpers", "demo_trainer.py")

FAST = {
    "EDL_TPU_TTL": "1",
    "EDL_TPU_GENERATOR_PERIOD": "0.2",
    "EDL_TPU_WATCHER_PERIOD": "0.2",
    "EDL_TPU_SUPERVISOR_PERIOD": "0.2",
    "EDL_TPU_BARRIER_TIMEOUT": "40",
    "EDL_TPU_RESIZE_BARRIER_TIMEOUT": "30",
}


def spawn_launcher(job_id, coord_ep, tmp, name, nodes_range, extra_env=None):
    env = dict(os.environ)
    env.update(FAST)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    log = open(os.path.join(tmp, f"launcher-{name}.log"), "wb")
    proc = subprocess.Popen(
        [sys.executable, "-m", "edl_tpu.collective.launch",
         "--job_id", job_id, "--coord_endpoints", coord_ep,
         "--nodes_range", nodes_range, "--nproc_per_node", "1",
         "--log_dir", os.path.join(tmp, f"log-{name}"), DEMO],
        env=env, cwd=tmp, stdout=log, stderr=subprocess.STDOUT)
    proc._logfile = log  # noqa: SLF001 - keep handle for closing
    return proc


def finish(proc, timeout):
    try:
        ret = proc.wait(timeout=timeout)
    finally:
        proc._logfile.close()  # noqa: SLF001
    return ret


@pytest.fixture
def coord(coord_server):
    ep = f"127.0.0.1:{coord_server.port}"
    client = CoordClient(ep)
    yield ep, client
    client.close()


def _dump_logs(tmp):
    out = []
    for root, _, files in os.walk(tmp):
        for f in files:
            if f.endswith(".log") or f.startswith("workerlog"):
                p = os.path.join(root, f)
                out.append(f"==== {p} ====\n" + open(p, errors="replace").read())
    return "\n".join(out)


def test_two_pod_job_succeeds(coord, tmp_path):
    ep, client = coord
    tmp = str(tmp_path)
    env = {"EDL_TPU_DEMO_SLEEP": "2"}
    a = spawn_launcher("j-ok", ep, tmp, "a", "2:2", env)
    b = spawn_launcher("j-ok", ep, tmp, "b", "2:2", env)
    ra, rb = finish(a, 60), finish(b, 60)
    assert (ra, rb) == (0, 0), _dump_logs(tmp)
    assert load_job_status(client, "j-ok") == Status.SUCCEED

    # relaunching a SUCCEEDed job is a no-op (reference launch.py:44-47)
    c = spawn_launcher("j-ok", ep, tmp, "c", "2:2", env)
    assert finish(c, 30) == 0


def test_trainer_failure_flags_job_failed(coord, tmp_path):
    ep, client = coord
    tmp = str(tmp_path)
    a = spawn_launcher("j-fail", ep, tmp, "a", "2:2", {"EDL_TPU_DEMO_SLEEP": "3"})
    b = spawn_launcher("j-fail", ep, tmp, "b", "2:2",
                       {"EDL_TPU_DEMO_SLEEP": "1", "EDL_TPU_DEMO_EXIT_CODE": "7"})
    rb = finish(b, 60)
    ra = finish(a, 60)
    assert rb == 1, _dump_logs(tmp)
    assert load_job_status(client, "j-fail") == Status.FAILED


def test_elastic_recovery_overwrites_failed_flag(coord, tmp_path):
    """A pod failure mid-job flags FAILED provisionally, but when the
    survivors complete, the leader's final verdict (current members only)
    flips the job to SUCCEED — elastic recovery must not read as failure."""
    ep, client = coord
    tmp = str(tmp_path)
    marker_a = os.path.join(tmp, "marker-a.txt")
    # a's two-pod trainer must outlast b's slowest path to its exit (when
    # b leads, its launcher hosts the world service before it spawns the
    # trainer that fails, then waits out the fail grace), or a finishes
    # SUCCEED as a non-leader and nobody overwrites b's FAILED.  When b
    # leads, its leaving restarts a solo at once; when a leads, a's
    # launcher tries a live reshard first, which the inert trainer ends
    # only by serving its sleep.
    a = spawn_launcher("j-recover", ep, tmp, "a", "1:2",
                       {"EDL_TPU_DEMO_SLEEP": "30", "EDL_TPU_DEMO_SLEEP_SOLO": "2",
                        "EDL_TPU_DEMO_MARKER": marker_a})
    b = spawn_launcher("j-recover", ep, tmp, "b", "1:2",
                       {"EDL_TPU_DEMO_SLEEP": "1", "EDL_TPU_DEMO_SLEEP_SOLO": "1",
                        "EDL_TPU_DEMO_EXIT_CODE": "7"})
    rb = finish(b, 60)
    ra = finish(a, 90)
    assert rb == 1 and ra == 0, _dump_logs(tmp)
    assert load_job_status(client, "j-recover") == Status.SUCCEED, \
        _dump_logs(tmp)
    # a ran beside b, then restarted solo after b left: the docstring's
    # scenario, whichever pod led the first stage
    starts_a = open(marker_a).read().strip().splitlines()
    assert len(starts_a) == 2, (starts_a, _dump_logs(tmp))
    assert "world=2" in starts_a[0] and "world=1" in starts_a[1], starts_a


def test_elastic_scale_out_restarts_trainers(coord, tmp_path):
    ep, client = coord
    tmp = str(tmp_path)
    marker_a = os.path.join(tmp, "marker-a.txt")
    marker_b = os.path.join(tmp, "marker-b.txt")
    # A starts solo (min 1) with a long solo sleep so B can join mid-run
    a = spawn_launcher("j-elastic", ep, tmp, "a", "1:2",
                       {"EDL_TPU_DEMO_SLEEP": "2", "EDL_TPU_DEMO_SLEEP_SOLO": "25",
                        "EDL_TPU_DEMO_MARKER": marker_a})
    # wait until A's solo trainer is actually running
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not os.path.exists(marker_a):
        time.sleep(0.2)
    assert os.path.exists(marker_a), _dump_logs(tmp)

    b = spawn_launcher("j-elastic", ep, tmp, "b", "1:2",
                       {"EDL_TPU_DEMO_SLEEP": "2", "EDL_TPU_DEMO_MARKER": marker_b})
    ra, rb = finish(a, 90), finish(b, 90)
    assert (ra, rb) == (0, 0), _dump_logs(tmp)
    assert load_job_status(client, "j-elastic") == Status.SUCCEED

    # A must have started twice: solo world=1, then resized world=2
    starts_a = open(marker_a).read().strip().splitlines()
    assert len(starts_a) == 2, starts_a
    assert "world=1" in starts_a[0] and "world=2" in starts_a[1]
    starts_b = open(marker_b).read().strip().splitlines()
    assert any("world=2" in s for s in starts_b)
