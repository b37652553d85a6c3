"""Elastic *training* end-to-end: real launchers, real JAX trainers,
real checkpoints, a live mid-run join.

This is SURVEY.md §7 step 4 (elastic resize proof) as a test, under the
shipped defaults (EDL_TPU_RESIZE_DELTA=1): pod A trains solo, pod B
joins mid-run, A's trainer re-forms the world IN PLACE (live reshard)
while B's fresh trainer restores from the peer cache, and the epoch
history records both world sizes.  Where the old leader is the pod that
leaves, the launcher falls back to stop-resume; the tests accept the
path the run actually took and say which from the logs.
"""

import os
import re
import subprocess
import sys
import time
import urllib.request

import pytest

from edl_tpu.cluster.status import Status, load_job_status
from edl_tpu.coord.client import CoordClient
from tests.test_launch_integration import FAST, finish

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAIN = os.path.join(REPO, "examples", "collective", "train_linear.py")


def spawn(job_id, coord_ep, tmp, name, ckpt_dir, extra_env=None,
          epochs="10", steps="4"):
    env = dict(os.environ)
    env.update(FAST)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["EDL_TPU_DEMO_STEP_SLEEP"] = "0.25"
    env["EDL_TPU_DEMO_MARKER"] = os.path.join(tmp, f"marker-{name}")
    env.update(extra_env or {})
    log = open(os.path.join(tmp, f"launcher-{name}.log"), "wb")
    proc = subprocess.Popen(
        [sys.executable, "-m", "edl_tpu.collective.launch",
         "--job_id", job_id, "--coord_endpoints", coord_ep,
         "--nodes_range", "1:2", "--nproc_per_node", "1",
         "--checkpoint_dir", ckpt_dir,
         "--log_dir", os.path.join(tmp, f"log-{name}"), TRAIN,
         "--", "--epochs", epochs, "--steps_per_epoch", steps],
        env=env, cwd=tmp, stdout=log, stderr=subprocess.STDOUT)
    proc._logfile = log  # noqa: SLF001
    return proc


@pytest.mark.slow
def test_sigterm_preemption_checkpoint(coord_server, tmp_path):
    """SIGTERM a 2-pod world mid-run: the signalled pod's launcher
    flags preemption, BOTH trainers checkpoint at an agreed step and
    exit PREEMPT_EXIT_CODE, the signalled pod departs DESCALED (exit
    0), and the survivor stop-resumes SOLO from the preemption-point
    checkpoint — epochs complete exactly once (VERDICT r4 #8)."""
    import signal as _signal

    ep = f"127.0.0.1:{coord_server.port}"
    ckpt = str(tmp_path / "ckpt")
    env = {"EDL_TPU_PREEMPT_CHECK_STEPS": "2"}
    pa = spawn("preempt-e2e", ep, str(tmp_path), "a", ckpt, extra_env=env,
               epochs="8", steps="4")
    pb = spawn("preempt-e2e", ep, str(tmp_path), "b", ckpt, extra_env=env,
               epochs="8", steps="4")
    # wait for the 2-pod world to commit its first epoch checkpoint
    deadline = time.time() + 240
    while time.time() < deadline:
        done = [d for d in (os.listdir(ckpt) if os.path.isdir(ckpt) else [])
                if d.isdigit()]
        if done:
            break
        assert pa.poll() is None and pb.poll() is None, "pod died in warmup"
        time.sleep(0.25)
    else:
        raise AssertionError("no checkpoint committed before preemption")

    pb.send_signal(_signal.SIGTERM)
    assert finish(pb, 240) == 0, "preempted pod must exit cleanly (DESCALED)"
    assert finish(pa, 300) == 0

    client = CoordClient(ep)
    assert load_job_status(client, "preempt-e2e") == Status.SUCCEED
    client.close()

    lb = (tmp_path / "launcher-b.log").read_bytes().decode(errors="replace")
    assert "flagging preemption" in lb, lb[-2000:]
    assert "preemption checkpoint complete; departing" in lb, lb[-2000:]
    # both worlds' trainers took the coordinated preemption checkpoint
    m = re.search(r"preemption flagged: checkpointing at step (\d+)", lb)
    assert m, lb[-3000:]
    preempt_step = int(m.group(1))
    la = (tmp_path / "launcher-a.log").read_bytes().decode(errors="replace")
    # the survivor's trainer snapshots and unwinds into a live reshard
    # (it never exits PREEMPT_EXIT_CODE, so its launcher never waits)
    assert "peer preempted: surviving in place" in la, la[-2000:]
    resumes = [int(x) for x in re.findall(r"resume_epoch=(\d+)", la)]
    if "live reshard complete" in la:
        # B was not the leader: A's process re-formed a solo world in
        # place from the preemption-point step
        assert f"step {preempt_step})" in la, la[-2000:]
    else:
        # B led the old world, so its launcher took the world service
        # with it: A's launcher stop-resumed, and the restarted trainer
        # resumed from the preemption-point checkpoint.  Its resume
        # epoch is the epoch the preempt step sat in (4 steps/epoch); a
        # preemption at an epoch-BOUNDARY step (step % 4 == 0) saves
        # with in_epoch still pointing at the just-finished epoch, so
        # the resume epoch is (step-1)//4 there and step//4 mid-epoch
        assert "re-barrier + restart trainers (stop-resume)" in la
        assert len(resumes) >= 2, la[-2000:]
        assert resumes[1] in (preempt_step // 4,
                              (preempt_step - 1) // 4), (resumes,
                                                         preempt_step)
    # the survivor finished the full epoch set exactly once, world=1
    marker_a = (tmp_path / "marker-a").read_text()
    done_lines = [l for l in marker_a.splitlines() if l.startswith("done")]
    assert done_lines, marker_a
    m = re.search(r"world=(\d+) epochs=\[([0-9, ]+)\]", done_lines[-1])
    assert m and m.group(1) == "1", marker_a
    assert [int(x) for x in m.group(2).split(",")] == list(range(8))


def _poll_metrics_endpoints(mdir, procs, want, deadline_s=240):
    """Scrape every addr file in ``mdir`` until all ``want`` series have
    nonzero counts (or every proc exits).  Returns the set seen."""
    from edl_tpu.obs.metrics import parse_exposition

    seen: set[str] = set()
    deadline = time.time() + deadline_s
    while time.time() < deadline and not want <= seen:
        for f in mdir.glob("metrics-*.addr"):
            addr = f.read_text().strip()
            try:
                with urllib.request.urlopen(f"http://{addr}/metrics",
                                            timeout=5) as resp:
                    text = resp.read().decode()
            except OSError:
                continue  # that process restarted/exited; others carry on
            samples = parse_exposition(text)  # raises if page is invalid
            for (name, _labels), value in samples.items():
                if name in want and value > 0:
                    seen.add(name)
        if all(p.poll() is not None for p in procs):
            break
        time.sleep(1.0)
    return seen


def _wait_for_checkpoints(ckpt, procs, n, deadline_s=180):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        done = [d for d in (os.listdir(ckpt) if os.path.isdir(ckpt) else [])
                if d.isdigit()]
        if len(done) >= n:
            return
        for p in procs:
            assert p.poll() is None, "pod died during warmup"
        time.sleep(0.25)
    raise AssertionError(f"never committed {n} epoch checkpoints")


def _complete_stages(ep, job):
    from edl_tpu.cluster.recovery import summarize_recovery
    client = CoordClient(ep)
    try:
        return [s for s in summarize_recovery(client, job) if "total" in s]
    finally:
        client.close()


@pytest.mark.slow
def test_peer_cache_restore_after_resize(coord_server, tmp_path):
    """ISSUE 2 acceptance: a mid-run join resizes the world; every
    trainer rebuilds its state from the surviving launcher's in-RAM
    cache — the survivor through the live reshard (``delta``), the
    joiner through the cache-first restore (``peer``) — and the
    restored state is verified bit-identical to the storage path in
    situ (EDL_TPU_MEMSTATE_VERIFY=1 restores BOTH and asserts equality
    inside the trainer)."""
    ep = f"127.0.0.1:{coord_server.port}"
    ckpt = str(tmp_path / "ckpt")
    env = {"EDL_TPU_MEMSTATE_VERIFY": "1"}
    pa = spawn("memstate-e2e", ep, str(tmp_path), "a", ckpt, extra_env=env)
    _wait_for_checkpoints(ckpt, [pa], 2)
    pb = spawn("memstate-e2e", ep, str(tmp_path), "b", ckpt, extra_env=env)
    assert finish(pa, 240) == 0
    assert finish(pb, 240) == 0

    client = CoordClient(ep)
    assert load_job_status(client, "memstate-e2e") == Status.SUCCEED
    client.close()
    complete = _complete_stages(ep, "memstate-e2e")
    assert complete, "no complete resize record"
    # no pod paid storage: "delta" as soon as one survivor resharded
    assert complete[-1]["restore_source"] == "delta", complete
    # both trainers logged the in-situ bit-identity proof (cache restore
    # AND storage restore of the same step compared leaf by leaf)
    la = (tmp_path / "launcher-a.log").read_bytes().decode(errors="replace")
    lb = (tmp_path / "launcher-b.log").read_bytes().decode(errors="replace")
    assert "reshard restore verified bit-identical to storage" in la, \
        la[-3000:]
    assert "restore_source=peer" in lb, lb[-3000:]
    assert "peer restore verified bit-identical to storage" in lb, \
        lb[-3000:]
    # the full epoch set still completed exactly once, world=2
    marker_a = (tmp_path / "marker-a").read_text()
    done = [l for l in marker_a.splitlines() if l.startswith("done")]
    m = re.search(r"world=(\d+) epochs=\[([0-9, ]+)\]", done[-1])
    assert m and m.group(1) == "2", marker_a
    assert [int(x) for x in m.group(2).split(",")] == list(range(10))


@pytest.mark.slow
def test_peer_cache_miss_falls_back_to_storage(coord_server, tmp_path):
    """Forced cache miss: a 1-byte cache cap rejects every shard push
    (eviction-class miss — the set never seals, no committed record),
    so the post-resize restore must fall back to Orbax storage and the
    recovery record says ``restore_source=storage``.  Same resize
    choreography as the peer-restore test; only the cache differs."""
    ep = f"127.0.0.1:{coord_server.port}"
    ckpt = str(tmp_path / "ckpt")
    env = {"EDL_TPU_MEMSTATE_MAX_BYTES": "1"}
    pa = spawn("miss-e2e", ep, str(tmp_path), "a", ckpt, extra_env=env)
    _wait_for_checkpoints(ckpt, [pa], 2)
    pb = spawn("miss-e2e", ep, str(tmp_path), "b", ckpt, extra_env=env)
    assert finish(pa, 240) == 0
    assert finish(pb, 240) == 0

    client = CoordClient(ep)
    assert load_job_status(client, "miss-e2e") == Status.SUCCEED
    client.close()
    complete = _complete_stages(ep, "miss-e2e")
    assert complete, "no complete resize record"
    assert complete[-1]["restore_source"] == "storage", complete
    la = (tmp_path / "launcher-a.log").read_bytes().decode(errors="replace")
    assert "restore_source=peer" not in la
    marker_a = (tmp_path / "marker-a").read_text()
    done = [l for l in marker_a.splitlines() if l.startswith("done")]
    assert done and "world=2" in done[-1], marker_a


@pytest.mark.slow
def test_elastic_join_resumes_training(coord_server, tmp_path):
    ep = f"127.0.0.1:{coord_server.port}"
    ckpt = str(tmp_path / "ckpt")
    mdir = tmp_path / "metrics"
    mdir.mkdir()
    # every process (launchers + trainers) serves /metrics on a free
    # port and advertises it via an addr file (doc/observability.md)
    obs_env = {"EDL_TPU_METRICS_PORT": "0", "EDL_TPU_METRICS_DIR": str(mdir)}
    pa = spawn("train-e2e", ep, str(tmp_path), "a", ckpt, extra_env=obs_env)
    # condition, not a fixed sleep (a loaded host made 12 s mean
    # anything from 1 to 6 epochs): B joins once A has COMMITTED at
    # least two epoch checkpoints solo
    deadline = time.time() + 180
    while time.time() < deadline:
        done = [d for d in (os.listdir(ckpt) if os.path.isdir(ckpt) else [])
                if d.isdigit()]
        if len(done) >= 2:
            break
        assert pa.poll() is None, "pod A died during solo warmup"
        time.sleep(0.25)
    else:
        raise AssertionError("pod A never committed 2 epoch checkpoints")
    pb = spawn("train-e2e", ep, str(tmp_path), "b", ckpt, extra_env=obs_env)
    # while the job runs, the live /metrics endpoints must serve valid
    # Prometheus text; after the resize the step-latency histogram (any
    # trainer) and the resize-phase histogram (the launchers) both have
    # samples.  _count series prove real observations, not just TYPE
    # lines.
    want = {"edl_train_step_seconds_count", "edl_resize_phase_seconds_count"}
    seen = _poll_metrics_endpoints(mdir, [pa, pb], want)
    assert want <= seen, f"missing live metrics series: {want - seen}"
    assert finish(pa, 240) == 0
    assert finish(pb, 240) == 0

    client = CoordClient(ep)
    assert load_job_status(client, "train-e2e") == Status.SUCCEED
    # the resize left a full recovery-time record (the north-star
    # metric): launcher phases + trainer restore/first-step merged.
    # Only COMPLETE records count — a stage whose trainer half never
    # landed (e.g. a second resize racing job completion) is legitimate
    # mid-flight state, not the record under test
    from edl_tpu.cluster.recovery import summarize_recovery
    stages = summarize_recovery(client, "train-e2e")
    complete = [s for s in stages if "total" in s]
    assert complete, stages
    assert 0 < complete[-1]["total"] < 300, stages
    print("recovery breakdown:", complete[-1])
    # the obs dump reproduces the same per-phase totals for the
    # completed resize — one read path over one write path
    from edl_tpu.obs.dump import job_report, render_report
    report = job_report(client, "train-e2e")
    assert [s for s in report["resizes"] if "total" in s] == complete
    assert "restored_to_first_step" in render_report(report)
    client.close()

    marker_a = (tmp_path / "marker-a").read_text()
    done = [l for l in marker_a.splitlines() if l.startswith("done")]
    assert done, marker_a
    # the finishing run saw world=2 and a full epoch set 0..9
    m = re.search(r"world=(\d+) epochs=\[([0-9, ]+)\] w_err=([0-9.]+)", done[-1])
    assert m, marker_a
    assert m.group(1) == "2"
    assert [int(x) for x in m.group(2).split(",")] == list(range(10))
    assert float(m.group(3)) < 0.05  # actually learned
    # A's trainer lived through the resize; B's joined at a nonzero epoch
    la = (tmp_path / "launcher-a.log").read_bytes().decode(errors="replace")
    lb = (tmp_path / "launcher-b.log").read_bytes().decode(errors="replace")
    assert "live reshard complete" in la, la[-3000:]
    assert re.findall(r"resume_epoch=(\d+)", la) == ["0"], la[-3000:]
    resumes_b = re.findall(r"resume_epoch=(\d+)", lb)
    assert resumes_b and int(resumes_b[-1]) > 0, resumes_b
