"""``python -m edl_tpu.obs.dump`` (also ``edl-obs-dump``): one-shot
human-readable reports of a job's observability state.

Two modes:

- **Store mode** (``--coord_endpoints`` + ``--job_id``): job summary +
  per-resize phase timeline.  The phase timeline is
  :func:`~edl_tpu.cluster.recovery.summarize_recovery` verbatim (the
  north-star recovery-time metric), so this CLI, the CSV collector, the
  controller's resize-cost signal, and the launcher/trainer trace
  events all report the same numbers: they share one read path over one
  write path (recovery.write_*_half).
- **Merge mode** (``--trace_dir`` [+ ``--merge``]): join every
  process's JSONL trace file in a shared directory — plus the
  aggregator's ``incidents-*.jsonl`` alert records — into causally
  ordered per-trace timelines (grouped by the ``trace_id`` the
  distributed context stamped on each event — obs/context.py), and
  optionally export Chrome/Perfetto ``trace_event`` JSON
  (``--perfetto out.json``) so "open the resize in Perfetto" is one
  command.  The reader tolerates a truncated final line (a concurrent
  writer mid-append): malformed lines are skipped and counted, never
  fatal.

Usage::

    python -m edl_tpu.obs.dump --coord_endpoints host:2379 --job_id rn50
    python -m edl_tpu.obs.dump ... --json     # machine-readable
    python -m edl_tpu.obs.dump ... --kill_time 1700000000.5   # adds
        kill_to_detect / total_from_kill (harness SIGKILL timestamp)
    python -m edl_tpu.obs.dump --merge --trace_dir /tmp/edl-trace \
        [--trace <trace_id>] [--perfetto resize.json]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

from edl_tpu.cluster.recovery import BUILD_FIELDS, summarize_recovery
from edl_tpu.obs.collector import collect_row

# render order: the chronological phase chain, then the totals —
# stop-resume phases first, then the delta-resize phases (a record
# carries one shape or the other; a fallback carries parts of both)
PHASE_ORDER = ("kill_to_detect", "detect_to_kill", "kill_to_barrier",
               "barrier_to_spawn", "detect_to_flag", "flag_to_barrier",
               "barrier_to_reshard", "spawn_to_restored",
               "restored_to_first_step", "total", "total_from_kill")


def job_report(store, job_id: str,
               kill_time: float | None = None) -> dict:
    """{"job": <collector row>, "resizes": <summarize_recovery>}."""
    return {"job": collect_row(store, job_id),
            "resizes": summarize_recovery(store, job_id, kill_time)}


def render_report(report: dict) -> str:
    row = report["job"]
    resizes = report["resizes"]
    lines = [
        f"job {row['job_id']}: {row['job_status']}"
        f"  stage={row['stage'] or '-'}"
        f"  pods={row['pods_running']}/{row['cluster_pods']}"
        f" (live {row['live_pods']})"
        f"  world={row['world_size']}"
        f"  train={row['train_status'] or '-'}"
        f"  resizes={row['resizes']}",
    ]
    for s in resizes:
        done = "" if "total" in s else "  [launcher half only]"
        src = (f"  restore_source={s['restore_source']}"
               if "restore_source" in s else "")
        mode = (f"  mode={s['resize_mode']}"
                if s.get("resize_mode", "stop_resume") != "stop_resume"
                else "")
        lines.append(f"  resize {s['stage']} @ {s['detect_at']:.3f}"
                     f"{done}{mode}{src}")
        for pod, reason in sorted(s.get("evicted", {}).items()):
            lines.append(f"    evicted {pod[:12]:<20} reason={reason}")
        for phase in PHASE_ORDER:
            if phase in s:
                lines.append(f"    {phase:<24} {s[phase]:>9.3f}s")
            if phase == "restored_to_first_step" and all(
                    f in s for f in BUILD_FIELDS):
                # what of it was building programs (obs/ledger.py)
                lines.append(
                    f"      trace+lower {s['build_trace_lower_s']:.3f}s"
                    f"  compile {s['build_compile_s']:.3f}s"
                    f"  compile cache {s['build_cache_hits']} hit(s) / "
                    f"{s['build_cache_misses']} miss(es)")
    if not resizes:
        lines.append("  (no resize records)")
    return "\n".join(lines)


# -- merged multi-process timelines ------------------------------------------

def read_trace_file(path: str) -> tuple[list[dict], int]:
    """Parse one JSONL trace file tolerantly: (events, skipped count).

    A live tracer may be mid-append when we read, so the final line can
    be truncated; any line that fails to parse as a JSON object is
    skipped and counted instead of failing the whole dump."""
    events: list[dict] = []
    skipped = 0
    with open(path, encoding="utf-8", errors="replace") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except ValueError:
                skipped += 1
                continue
            if isinstance(ev, dict) and "name" in ev:
                events.append(ev)
            else:
                skipped += 1
    return events, skipped


def read_trace_dir(trace_dir: str) -> tuple[list[dict], int]:
    """Every ``trace-*.jsonl`` (and rotated ``.jsonl.1``) in the shared
    directory, plus the aggregator's ``incidents-*.jsonl`` alert
    records — rotated generations included, since
    ``EDL_TPU_TRACE_MAX_MB`` caps incident files the same way
    (:mod:`edl_tpu.obs.rules` writes them trace-event-shaped
    and stamped with the job's generation trace_id, so a firing alert
    lands inside the causal timeline of the resize/hang it belongs to);
    events are tagged with their source ``file`` so merged views can
    attribute each event to a process."""
    events: list[dict] = []
    skipped = 0
    paths = sorted(glob.glob(os.path.join(trace_dir, "trace-*.jsonl"))
                   + glob.glob(os.path.join(trace_dir, "trace-*.jsonl.1"))
                   + glob.glob(os.path.join(trace_dir, "incidents-*.jsonl"))
                   + glob.glob(os.path.join(trace_dir,
                                            "incidents-*.jsonl.1")))
    for path in paths:
        try:
            evs, bad = read_trace_file(path)
        except OSError:
            continue  # a file deleted mid-scan is not an error
        base = os.path.basename(path)
        if base.endswith(".jsonl.1"):
            # a rotated generation is the SAME process as its live file
            # — one pid row in Perfetto, one process in the timeline
            base = base[:-len(".1")]
        for e in evs:
            e.setdefault("file", base)
        events.extend(evs)
        skipped += bad
    return events, skipped


def merge_timeline(events: list[dict],
                   trace_id: str | None = None) -> list[dict]:
    """Causally-ordered view: filter to one trace (when given) and sort
    by wall-clock begin (``ts`` is the span BEGIN for dur events)."""
    if trace_id is not None:
        events = [e for e in events if e.get("trace_id") == trace_id]
    return sorted(events, key=lambda e: (float(e.get("ts", 0.0)),
                                         str(e.get("name", ""))))


def to_perfetto(events: list[dict]) -> dict:
    """Chrome/Perfetto ``trace_event`` JSON: spans (events with ``dur``)
    become complete ``"X"`` events, instants become ``"i"``; each source
    process (trace file) gets its own pid row named by its component, so
    the cross-process causal chain reads as parallel tracks.

    Events carrying a ``counters`` dict (the step-phase ledger's
    ``train/step_phases``, the goodput ledger's ``goodput/sample``)
    additionally emit a ``"C"`` **counter** sample on a per-event-name
    track, so a resize's badput and the surrounding steps' phase split
    render as stacked counter graphs in the SAME view as the resize's
    handshake spans."""
    core = {"ts", "name", "dur", "component", "file", "counters"}
    pids: dict[str, int] = {}
    trace_events: list[dict] = []
    for e in events:
        src = str(e.get("file", e.get("component", "proc")))
        pid = pids.get(src)
        if pid is None:
            pid = pids[src] = len(pids) + 1
            trace_events.append({
                "name": "process_name", "ph": "M", "pid": pid, "tid": pid,
                "args": {"name": f"{e.get('component', 'proc')} [{src}]"}})
        args = {k: v for k, v in e.items() if k not in core}
        rec = {"name": str(e.get("name", "?")),
               "cat": str(e.get("component", "edl")),
               "pid": pid, "tid": pid,
               "ts": round(float(e.get("ts", 0.0)) * 1e6, 3),
               "args": args}
        dur = e.get("dur")
        if isinstance(dur, (int, float)):
            rec["ph"] = "X"
            rec["dur"] = round(float(dur) * 1e6, 3)
        else:
            rec["ph"] = "i"
            rec["s"] = "p"
        trace_events.append(rec)
        counters = e.get("counters")
        if isinstance(counters, dict):
            vals = {str(k): float(v) for k, v in counters.items()
                    if isinstance(v, (int, float))}
            if vals:
                trace_events.append({
                    "name": str(e.get("name", "?")), "ph": "C",
                    "pid": pid, "tid": pid,
                    "ts": round(float(e.get("ts", 0.0)) * 1e6, 3),
                    "args": vals})
    return {"traceEvents": trace_events, "displayTimeUnit": "ms"}


def render_timeline(events: list[dict]) -> str:
    """Per-trace text timelines: events grouped by trace_id (traces
    ordered by first event), offsets relative to each trace's start."""
    if not events:
        return "(no trace events)"
    by_trace: dict[str | None, list[dict]] = {}
    for e in events:
        by_trace.setdefault(e.get("trace_id"), []).append(e)
    blocks: list[str] = []
    ordered = sorted(by_trace.items(),
                     key=lambda kv: float(kv[1][0].get("ts", 0.0)))
    for tid, evs in ordered:
        procs = {e.get("file", e.get("component", "?")) for e in evs}
        head = (f"trace {tid}" if tid else "untraced events")
        blocks.append(f"{head}  ({len(evs)} events, "
                      f"{len(procs)} process{'es' if len(procs) != 1 else ''})")
        t0 = float(evs[0].get("ts", 0.0))
        for e in evs:
            off = float(e.get("ts", 0.0)) - t0
            comp = str(e.get("component", "?"))
            line = f"  +{off:9.3f}s  {comp:<10} {e.get('name', '?')}"
            if isinstance(e.get("dur"), (int, float)):
                line += f"  dur={float(e['dur']):.3f}s"
            extras = {k: v for k, v in e.items()
                      if k not in ("ts", "name", "dur", "component", "file",
                                   "trace_id", "span_id", "parent_id")}
            if extras:
                line += "  " + " ".join(f"{k}={v}"
                                        for k, v in sorted(extras.items()))
            blocks.append(line)
    return "\n".join(blocks)


def _run_merge(args) -> int:
    events, skipped = read_trace_dir(args.trace_dir)
    if skipped:
        print(f"[edl-obs-dump] skipped {skipped} malformed trace line(s) "
              "(concurrent writer?)", file=sys.stderr)
    merged = merge_timeline(events, args.trace_id)
    if args.perfetto:
        with open(args.perfetto, "w", encoding="utf-8") as f:
            json.dump(to_perfetto(merged), f)
        print(f"[edl-obs-dump] wrote {len(merged)} events to "
              f"{args.perfetto} (open in Perfetto / chrome://tracing)",
              file=sys.stderr)
    if args.as_json:
        print(json.dumps({"events": merged, "skipped_lines": skipped}))
    else:
        print(render_timeline(merged))
    return 0


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        "edl_tpu.obs.dump",
        description="Render a job's per-resize phase timeline + summary "
                    "from the coordination store, or merge a shared trace "
                    "directory into per-trace timelines (--merge)")
    p.add_argument("--coord_endpoints")
    p.add_argument("--job_id", nargs="+")
    p.add_argument("--kill_time", type=float, default=None,
                   help="harness SIGKILL timestamp: adds kill_to_detect "
                        "and total_from_kill to each complete resize")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit machine-readable JSON instead of text")
    p.add_argument("--merge", action="store_true",
                   help="merge-mode: join multi-process trace files by "
                        "trace_id (requires --trace_dir)")
    p.add_argument("--trace_dir", default=None,
                   help="shared EDL_TPU_TRACE_DIR holding each process's "
                        "trace-<component>-<pid>.jsonl")
    p.add_argument("--trace", dest="trace_id", default=None,
                   help="restrict merge-mode output to one trace_id")
    p.add_argument("--perfetto", metavar="OUT_JSON", default=None,
                   help="merge-mode: also write Chrome/Perfetto "
                        "trace_event JSON")
    args = p.parse_args(argv)

    if args.merge or args.trace_dir:
        if not args.trace_dir:
            p.error("--merge requires --trace_dir")
        return _run_merge(args)

    if not args.coord_endpoints or not args.job_id:
        p.error("store mode requires --coord_endpoints and --job_id "
                "(or use --merge --trace_dir)")
    from edl_tpu.coord.client import connect
    store = connect(args.coord_endpoints)
    try:
        for job_id in args.job_id:
            report = job_report(store, job_id, kill_time=args.kill_time)
            if args.as_json:
                print(json.dumps(report))
            else:
                print(render_report(report))
    finally:
        store.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
