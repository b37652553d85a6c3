"""On-demand profiler capture: ``/profile?duration_s=N`` on the
process that is actually training.

The old profiling story was a config-time window (``profile_window``,
steps 100–105, rank 0) — useless against a straggler that shows up on
day three.  This module makes capture a *runtime* request:

- :class:`ProfileCapture` owns one capture at a time for its process.
  ``trigger()`` starts a background worker that runs ``jax.profiler``
  for ``duration_s`` seconds (TensorBoard-loadable trace directory) —
  or, on the CPU backend / when the profiler is unavailable, arms the
  step ledger's capture window instead
  (:meth:`~edl_tpu.obs.ledger.StepPhaseLedger.start_capture`: one
  ``train/step_phases`` trace event per step, exact per-phase split);
- every capture writes a JSON **manifest** into ``EDL_TPU_PROFILE_DIR``
  (default: ``EDL_TPU_TRACE_DIR``, else ``/tmp/edl-tpu-profile``)
  carrying the process's current generation ``trace_id`` — and emits a
  ``profile/capture`` trace event, so the capture joins the job's
  ``edl-obs-dump --merge`` causal timeline next to whatever resize or
  alert provoked it;
- :func:`install_route` mounts the capture at ``/profile`` on the
  process's /metrics endpoint (:mod:`edl_tpu.obs.exposition` routes) —
  the surface the aggregator's **alert action hook** calls: a firing
  ``trainer-straggler`` / ``gateway-p99-slo`` alert requests a capture
  on the suspect instance automatically (:mod:`edl_tpu.obs.rules`
  ``action="profile"`` + the aggregator's action handler).

Knobs: ``EDL_TPU_PROFILE_DIR`` (artifact/manifest directory),
``EDL_TPU_PROFILE_DURATION`` (default seconds per capture, 5).
"""

from __future__ import annotations

import json
import os
import threading
import time

from edl_tpu.obs import context as obs_context
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.utils.logger import get_logger

logger = get_logger(__name__)

CAPTURES_TOTAL = obs_metrics.counter(
    "edl_profile_captures_total",
    "Profiler captures completed, by kind (jax_profiler vs the "
    "phase_ledger CPU fallback) and trigger (http vs alert)",
    ("kind", "trigger"))


def default_duration() -> float:
    try:
        return float(os.environ.get("EDL_TPU_PROFILE_DURATION", 5.0))
    except ValueError:
        return 5.0


def profile_dir() -> str:
    return (os.environ.get("EDL_TPU_PROFILE_DIR")
            or os.environ.get("EDL_TPU_TRACE_DIR")
            or "/tmp/edl-tpu-profile")


def _jax_profiler_usable() -> bool:
    """True when jax.profiler capture is worth attempting: an already-
    initialized non-CPU backend.  The CPU backend takes the ledger
    fallback — deterministic, near-free, and exactly what the phase
    breakdown is for."""
    try:
        import jax
        from jax._src import xla_bridge
        if not getattr(xla_bridge, "_backends", None):
            return False  # probing would CREATE a backend — never do that
        return jax.default_backend() != "cpu"
    # edl-lint: disable=wire-error — capability probe: False (take the
    # ledger fallback) IS the answer for "no usable jax profiler"
    except Exception:  # noqa: BLE001 — no jax, no profiler
        return False


class ProfileCapture:
    """One capture at a time for this process; ``trigger`` returns
    immediately (the capture runs on a daemon worker)."""

    def __init__(self, component: str = "trainer", ledger=None,
                 out_dir: str | None = None):
        self.component = component
        self.ledger = ledger
        self.out_dir = out_dir or profile_dir()
        self._lock = threading.Lock()
        self._active: dict | None = None
        self._seq = 0

    def trigger(self, duration_s: float | None = None,
                trigger: str = "http") -> dict:
        duration_s = (default_duration() if not duration_s
                      else min(300.0, max(0.05, float(duration_s))))
        # the requesting thread's ambient context (falls back to the
        # process root — the generation trace in launcher-spawned
        # trainers), captured HERE: the worker thread has no ambient
        ctx = obs_context.current()
        trace_id = ctx.trace_id if ctx is not None else None
        with self._lock:
            if self._active is not None:
                return {"busy": True, **self._active}
            self._seq += 1
            # a DISABLED ledger must not pretend to capture: its
            # step_done is a no-op, so the "capture" would be a manifest
            # pointing at a trace that never receives step events
            ledger_ok = (self.ledger is not None
                         and getattr(self.ledger, "enabled", False))
            kind = ("jax_profiler" if _jax_profiler_usable()
                    else "phase_ledger" if ledger_ok
                    else "manifest_only")
            name = f"profile-{self.component}-{os.getpid()}-{self._seq}"
            manifest = {
                "name": name, "kind": kind, "component": self.component,
                "pid": os.getpid(), "trigger": trigger,
                "duration_s": duration_s, "ts": round(time.time(), 6),
            }
            if trace_id:
                manifest["trace_id"] = trace_id
            self._active = manifest
        threading.Thread(target=self._run, args=(dict(manifest),),
                         daemon=True, name=f"edl-profile:{name}").start()
        return {"started": True, **manifest,
                "manifest": os.path.join(self.out_dir, name + ".json")}

    def _run(self, manifest: dict) -> None:
        duration_s = manifest["duration_s"]
        kind = manifest["kind"]
        t0 = time.monotonic()
        # the capture window REMAINING: a jax-profiler attempt that
        # fails only at stop_trace has already slept the whole window —
        # the fallback must not sleep it a second time (the capture
        # slot would read busy for 2x the requested duration)
        remaining = duration_s
        try:
            if kind == "jax_profiler":
                artifact = os.path.join(self.out_dir, manifest["name"])
                started = False
                try:
                    import jax
                    os.makedirs(artifact, exist_ok=True)
                    jax.profiler.start_trace(artifact)
                    started = True
                    time.sleep(duration_s)
                    jax.profiler.stop_trace()
                    manifest["artifact"] = artifact
                except Exception:  # noqa: BLE001 — degrade, never crash the host
                    logger.exception("jax.profiler capture failed; "
                                     "falling back to the phase ledger")
                    if started:
                        # a failed stop leaves the profiler session
                        # open — every later start_trace would then
                        # fail too.  Best-effort close it now.
                        try:
                            jax.profiler.stop_trace()
                        # edl-lint: disable=wire-error — second-chance
                        # close: "no trace running" is the good case
                        except Exception:  # noqa: BLE001
                            pass
                    remaining = max(0.0,
                                    duration_s - (time.monotonic() - t0))
                    kind = manifest["kind"] = (
                        "phase_ledger"
                        if self.ledger is not None
                        and getattr(self.ledger, "enabled", False)
                        and remaining >= 0.05
                        else "manifest_only")
            if kind == "phase_ledger":
                # the step loop emits per-step train/step_phases events
                # into the process trace file for the window
                self.ledger.start_capture(remaining)
                time.sleep(remaining)
                tr = obs_trace.get_tracer()
                if getattr(tr, "path", None):
                    manifest["artifact"] = tr.path
            elif kind == "manifest_only":
                time.sleep(min(remaining, 0.05))
            manifest["captured_s"] = round(time.monotonic() - t0, 3)
            self._write_manifest(manifest)
            CAPTURES_TOTAL.labels(kind=manifest["kind"],
                                  trigger=manifest["trigger"]).inc()
            extra = ({"trace_id": manifest["trace_id"]}
                     if manifest.get("trace_id") else {})
            obs_trace.emit("profile/capture", dur=manifest["captured_s"],
                           # edl-lint: disable=clock — back-dating a TRACE
                           # ts to the capture begin (merge convention: ts
                           # is begin), not deadline arithmetic
                           at=time.time() - manifest["captured_s"],
                           kind=manifest["kind"],
                           trigger=manifest["trigger"],
                           capture=manifest["name"],
                           path=manifest.get("artifact", ""), **extra)
        except Exception:  # noqa: BLE001 — profiling must never kill the host
            logger.exception("profile capture failed")
        finally:
            with self._lock:
                self._active = None

    def _write_manifest(self, manifest: dict) -> None:
        path = os.path.join(self.out_dir, manifest["name"] + ".json")
        try:
            os.makedirs(self.out_dir, exist_ok=True)
            # whole or absent: whoever polls for the file reads it next
            with open(path + ".tmp", "w", encoding="utf-8") as f:
                json.dump(manifest, f)
            os.replace(path + ".tmp", path)
        except OSError:
            logger.exception("profile manifest write failed")


def install_route(capture: ProfileCapture) -> None:
    """Mount ``capture`` at ``/profile`` on this process's /metrics
    endpoint (idempotent: last registration wins)."""
    from edl_tpu.obs import exposition

    def handle(query: dict) -> dict:
        duration = exposition.query_float(query, "duration_s")
        return capture.trigger(duration_s=duration or None,
                               trigger=str(query.get("trigger", "http")))

    exposition.register_route("/profile", handle)
