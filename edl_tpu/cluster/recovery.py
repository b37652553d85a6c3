"""Elastic recovery-time records: read + merge the per-stage timing
halves written by the launcher (detect/killed/barrier/spawn —
collective/launcher.py) and the trainer (restored/first_step —
train/trainer.py).

This is the north-star metric the reference never published
(BASELINE.md "Not published: elastic resize recovery time — must be
measured by the new framework"): how long from noticing a membership
change until the resized world has taken its first real training step.
"""

from __future__ import annotations

import json

from edl_tpu.cluster import paths
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.utils import constants

# phase name -> (begin timestamp key, end timestamp key), per record half.
# summarize_recovery, the per-phase histogram, and the trace events are
# all derived from these tables and the same ``times`` dicts, so the
# store record and the trace agree by construction.  A stop-resume
# record carries detect/killed/barrier/spawn; a delta-resize record
# (``resize_mode=delta`` — surviving trainers resharded in place,
# collective/launcher.py) carries detect/flagged/barrier/reshard_done
# instead, and a fallback record has BOTH flagged and killed (the delta
# attempt is inside detect_to_kill).  Phases whose keys are absent are
# simply skipped, so the two shapes share one write path.
LAUNCHER_PHASES = (
    ("detect_to_kill", "detect", "killed"),
    ("kill_to_barrier", "killed", "barrier"),
    ("barrier_to_spawn", "barrier", "spawn"),
    ("detect_to_flag", "detect", "flagged"),
    ("flag_to_barrier", "flagged", "barrier"),
    ("barrier_to_reshard", "barrier", "reshard_done"),
)
TRAINER_PHASES = (
    ("restored_to_first_step", "restored", "first_step"),
)

RESIZE_PHASE_SECONDS = obs_metrics.histogram(
    "edl_resize_phase_seconds",
    "Elastic resize phase duration in seconds, by phase",
    ("phase",), buckets=obs_metrics.RESIZE_BUCKETS)


def _observe_phases(stage: str, times: dict, phases) -> None:
    tracer = obs_trace.get_tracer()
    for phase, begin, end in phases:
        if begin in times and end in times:
            # clamp: a delta-resize FALLBACK kills trainers after its
            # barrier, so kill_to_barrier would come out negative there
            dur = max(0.0, times[end] - times[begin])
            RESIZE_PHASE_SECONDS.labels(phase=phase).observe(dur)
            tracer.emit(f"resize/{phase}", at=times[begin], dur=dur,
                        stage=stage)


def write_launcher_half(store, job_id: str, stage: str, pod_id: str,
                        times: dict) -> None:
    """Launcher half of a resize record (detect/killed/barrier/spawn
    wall-clock timestamps): one write drives the store record (merged
    back by :func:`summarize_recovery`), the resize-phase histogram,
    and the JSONL trace events."""
    store.put(
        paths.key(job_id, constants.ETCD_RECOVERY,
                  f"{stage}/launcher/{pod_id}"),
        json.dumps(times).encode())
    _observe_phases(stage, times, LAUNCHER_PHASES)


# what the trainer built between ``restored`` and ``first_step``, from
# the program-build ledger (obs/ledger.py): whether
# restored_to_first_step was Python (trace + lower), XLA (compile: a
# persistent-cache miss compiles, a hit reads) or the step itself
BUILD_FIELDS = ("build_trace_lower_s", "build_compile_s",
                "build_cache_hits", "build_cache_misses")


def build_fields(before: dict, after: dict) -> dict:
    """:data:`BUILD_FIELDS` from two ``ProgramBuildLedger.totals()``
    (flat ``<kind>/<component>/<family>/<field>`` keys), every label."""
    def grown(*fields):
        return sum(v - before.get(k, 0) for k, v in after.items()
                   if k.rsplit("/", 1)[-1] in fields)
    return {"build_trace_lower_s": round(grown("trace_s", "lower_s"), 3),
            "build_compile_s": round(grown("compile_s"), 3),
            "build_cache_hits": int(grown("cache_hits")),
            "build_cache_misses": int(grown("cache_misses"))}


def write_trainer_half(store, job_id: str, stage: str, pod_id: str,
                       restored: float, first_step: float,
                       restore_source: str | None = None,
                       builds: dict | None = None) -> None:
    """Trainer half (checkpoint restored / first post-resize step) —
    same unified write path as :func:`write_launcher_half`.
    ``restore_source`` records where the state came from:
    ``"peer"`` (memstate in-RAM cache) or ``"storage"`` (Orbax) — the
    cache-vs-storage split is the thing the memstate subsystem exists
    to move, so it lives in the same record as the phase timings.
    ``builds`` (:func:`build_fields`) says what of the interval was
    building programs."""
    times = {"restored": restored, "first_step": first_step}
    if restore_source is not None:
        times["restore_source"] = restore_source
    times.update(builds or {})
    store.put(
        paths.key(job_id, constants.ETCD_RECOVERY,
                  f"{stage}/trainer/{pod_id}"),
        json.dumps(times).encode())
    _observe_phases(stage, times, TRAINER_PHASES)


def load_recovery_records(store, job_id: str) -> dict[str, dict]:
    """{stage: {"launcher": {pod: times}, "trainer": {pod: times}}}."""
    prefix = paths.table_prefix(job_id, constants.ETCD_RECOVERY)
    recs, _rev = store.get_prefix(prefix)
    out: dict[str, dict] = {}
    for rec in recs:
        stage, role, pod = rec.key[len(prefix):].split("/", 2)
        out.setdefault(stage, {}).setdefault(role, {})[pod] = json.loads(
            rec.value.decode())
    return out


def summarize_recovery(store, job_id: str,
                       kill_time: float | None = None) -> list[dict]:
    """One breakdown dict per completed resize stage, oldest first.

    Phases (seconds): ``detect_to_kill`` (terminate old trainers),
    ``kill_to_barrier`` (membership re-agreement), ``barrier_to_spawn``
    (respawn), ``spawn_to_restored`` (jax + checkpoint restore),
    ``restored_to_first_step`` (recompile + first step; the
    :data:`BUILD_FIELDS` of the trainer that closed the resize say how
    much of it was tracing and lowering, how much compiling, and
    whether the compile cache hit), ``total`` =
    detect → first post-resize step.  With ``kill_time`` (the harness's
    SIGKILL timestamp) also ``kill_to_detect`` (lease TTL + generator +
    watcher latency) and ``total_from_kill``."""
    out = []
    for stage, halves in load_recovery_records(store, job_id).items():
        launchers = halves.get("launcher", {})
        trainers = halves.get("trainer", {})
        if not launchers:
            continue
        # earliest detector is the canonical launcher record; the last
        # trainer to finish its first step closes the resize
        lt = min(launchers.values(), key=lambda t: t["detect"])
        mode = lt.get("resize_mode",
                      "delta" if "reshard_done" in lt else "stop_resume")
        entry = {
            "stage": stage,
            "resize_mode": mode,
            "detect_at": round(lt["detect"], 3),
        }
        # reasoned departures (preempt flag carried an eviction reason:
        # descale / priority-yield / straggler-evict / sigterm) — merged
        # across every launcher half so one pod's store blip can't lose
        # the why; edl-obs-dump timelines render it
        evicted: dict[str, str] = {}
        for t in launchers.values():
            if isinstance(t.get("evicted"), dict):
                evicted.update(t["evicted"])
        if evicted:
            entry["evicted"] = evicted
        for phase, begin, end in LAUNCHER_PHASES:
            if begin in lt and end in lt:
                entry[phase] = round(max(0.0, lt[end] - lt[begin]), 3)
        # the handoff into the trainer half: respawn for stop-resume,
        # the in-place reshard ack for delta
        hand = lt.get("spawn", lt.get("reshard_done"))
        if trainers:
            tt = max(trainers.values(), key=lambda t: t["first_step"])
            if hand is not None:
                entry["spawn_to_restored"] = round(
                    max(0.0, tt["restored"] - hand), 3)
            entry.update({
                "restored_to_first_step": round(
                    tt["first_step"] - tt["restored"], 3),
                "total": round(tt["first_step"] - lt["detect"], 3),
            })
            # what that trainer was building in it (absent from an
            # older trainer's half)
            entry.update({f: tt[f] for f in BUILD_FIELDS if f in tt})
            # "peer"/"delta" only when EVERY pod restored from the
            # cache — one storage fallback means the resize still paid
            # storage
            sources = {t.get("restore_source") for t in trainers.values()}
            if sources != {None}:
                if sources <= {"peer", "delta"}:
                    entry["restore_source"] = (
                        "delta" if "delta" in sources else "peer")
                else:
                    entry["restore_source"] = "storage"
            if kill_time is not None:
                entry["kill_to_detect"] = round(lt["detect"] - kill_time, 3)
                entry["total_from_kill"] = round(
                    tt["first_step"] - kill_time, 3)
        out.append(entry)
    out.sort(key=lambda e: e["detect_at"])  # chronological, oldest first
    return out
