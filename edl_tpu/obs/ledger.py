"""Per-step phase ledger: where does a train step's wall time go?

Step latency histograms say *that* a job slowed down; this module says
*why*.  Each completed step's wall time is decomposed into named
phases —

- ``data_wait``  — blocked obtaining the next host batch (the prefetch
  queue ran dry: input-bound time);
- ``h2d``        — blocked on host→device staging (`shard_host_batch`
  / `device_put` waits not hidden behind compute);
- ``compute``    — blocked dispatching the jitted step (with a bounded
  dispatch queue the block lands here, so in steady state this
  converges on device step time);
- ``hooks``      — span marking, heartbeat, preempt/reshard checks,
  logging — the framework's own per-step bookkeeping;
- ``checkpoint`` — save/wait calls landing inside the epoch loop

— published as ``edl_step_phase_seconds{phase}`` histograms.  Phases
nest correctly: a phase recorded while another is open is *deducted*
from the enclosing one (``h2d`` waits surface inside the consumer's
``data_wait``), so the per-step sum never double counts.

**Self-check**: the ledger tracks what fraction of step wall time its
phases account for (``edl_step_ledger_coverage_ratio``, an EMA).  The
CI profiling smoke gates it ≥ 0.95 — if instrumentation drifts off
the hot path's real shape, the gauge says so before anyone trusts a
breakdown.

The ledger is also the CPU fallback for on-demand profiler capture
(:mod:`edl_tpu.obs.profile`): while a capture window is armed
(:meth:`StepPhaseLedger.start_capture`), every step emits a
``train/step_phases`` trace event carrying the per-phase split as a
``counters`` dict — ``edl-obs-dump --perfetto`` renders those as
counter tracks next to the span rows.  Outside a capture window the
same event is emitted on a throttled cadence (~`_EMIT_EVERY_S`), so
long-running jobs always have a coarse phase history in their trace.

**One instrument, two loops.**  The class is parametrised by its
phase tuple, histogram and component: the trainer's epoch loop uses the
defaults above; ``ContinuousBatcher``'s engine thread builds one over
its tick phases (``serving/engine.py``: ``edl_engine_tick_phase_seconds``,
component ``engine``).  A ``with ledger.phase(name):`` block gives
three things at once: exclusive host seconds into the histogram and
into cumulative totals (:meth:`StepPhaseLedger.totals`: what
``stats()`` and the benchmark difference), a
``jax.profiler.TraceAnnotation("<component>/<name>")`` so the same span
sits in a profiler capture on the device's clock (``train/compute``,
``engine/sync``, ... in ``/profile`` or the benchmark's traced run;
:func:`edl_tpu.obs.trace.annotation`, a no-op without JAX), and the
coverage self-check.  There is no switch in the environment: the
benchmark reads the ledger, and ``enabled=False`` is for tests.

**The request-scoped sibling** (:class:`RequestStageLedger`).  A tick
has phases; a request has STAGES (``queue_wait``, ``prefill``,
``decode``, ...) that overlap other requests' and outlive any tick, so
they are not spans of a loop but durations between stamps.  One
``observe(stage, seconds, lane=...)`` feeds a cumulative sum and count
for means, the same by ``lane`` where the stage has lanes, and, for a
stage whose TAILS are read, its registry histogram (the operator's
view) and cumulative bucket counts on ONE geometric ladder
(:data:`STAGE_EDGES`); :meth:`RequestStageLedger.totals` returns them
as flat numeric keys, so whoever differences ``stats()`` at two
instants gets the window's percentiles with no span handed over
(``benchmarks/layer_metrics/engine_queue_wait_p90_s.py`` is such a
reader).
"""

from __future__ import annotations

import math
import time
from bisect import bisect_left
from contextlib import contextmanager
from itertools import accumulate

from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace

PHASES = ("data_wait", "h2d", "compute", "hooks", "checkpoint")

PHASE_SECONDS = obs_metrics.histogram(
    "edl_step_phase_seconds",
    "Per-step wall time by phase: data_wait / h2d / compute / hooks / "
    "checkpoint (train/trainer.py step ledger)",
    ("phase",))
_COVERAGE_G = obs_metrics.gauge(
    "edl_step_ledger_coverage_ratio",
    "EMA fraction of step wall time the phase ledger accounts for "
    "(self-check; ~1.0 when instrumentation covers the hot path)")

# throttled background trace emit (outside capture windows)
_EMIT_EVERY_S = 30.0


class StepPhaseLedger:
    """One instance per loop; NOT thread-safe by design — every call
    happens on the loop's own thread (the trainer's epoch loop,
    including the ``h2d``/``data_wait`` credits from generators it
    drives; the engine's tick thread).  :meth:`totals` alone may be
    called from another thread, under whatever lock the owner holds
    around :meth:`step_done`.

    ``phases``/``histogram``/``coverage_gauge`` default to the
    trainer's; ``component`` prefixes the profiler annotations and
    names the trace event (``<component>/step_phases``).
    ``idle_phase`` names a phase that is time BETWEEN steps (the
    engine blocked waiting for requests): observed and totalled like
    any other, but taken out of the step's wall time and of the
    coverage check.  ``overhead_phase`` is charged the ledger's own
    close-out cost."""

    def __init__(self, enabled: bool = True, component: str = "train",
                 phases: tuple[str, ...] = PHASES,
                 histogram=PHASE_SECONDS, coverage_gauge=_COVERAGE_G,
                 idle_phase: str | None = None,
                 overhead_phase: str = "hooks"):
        self.enabled = enabled
        self.component = component
        self.phases = tuple(phases)
        self._histogram = histogram
        self._coverage_gauge = coverage_gauge
        self._idle_phase = idle_phase
        self._overhead_phase = overhead_phase
        self._event = f"{component}/step_phases"
        self._acc = dict.fromkeys(self.phases, 0.0)
        self._open: list[list[float]] = []   # stack of [deduction] frames
        self._cover_ema: float | None = None
        # since construction (totals()): what stats() and the benchmark
        # difference; written in step_done only
        self._cum = dict.fromkeys(self.phases, 0.0)
        self._cum_wall = 0.0
        self._steps = 0
        self._totals = dict.fromkeys(self.phases, 0.0)  # since last emit
        self._totals_wall = 0.0
        self._totals_steps = 0
        self._last_emit = time.monotonic()
        self._capture_until = 0.0            # monotonic deadline

    # -- recording -----------------------------------------------------------
    @contextmanager
    def phase(self, name: str, **args):
        """Time the block into ``name``.  Credits recorded inside the
        block (a nested phase, an external :meth:`add`) are deducted,
        so enclosing phases report only their own exclusive time.  The
        block is also a profiler annotation ``<component>/<name>``,
        with ``args`` as the span's arguments in a capture."""
        if not self.enabled:
            yield
            return
        frame = [0.0]
        self._open.append(frame)
        t0 = time.perf_counter()
        try:
            with obs_trace.annotation(f"{self.component}/{name}", **args):
                yield
        finally:
            dt = time.perf_counter() - t0
            self._open.pop()
            # exclusive time: the block minus everything credited inside
            # it; the ENCLOSING phase deducts this block's whole span
            self._acc[name] = (self._acc.get(name, 0.0)
                               + max(0.0, dt - frame[0]))
            if self._open:
                self._open[-1][0] += dt

    def add(self, name: str, seconds: float) -> None:
        """Credit ``seconds`` to ``name`` directly — for waits measured
        by code the loop drives (the ``h2d`` stage wait inside the
        prefetch generator) rather than a wrappable block."""
        if self.enabled:
            self._credit(name, max(0.0, float(seconds)))

    def _credit(self, name: str, seconds: float) -> None:
        self._acc[name] = self._acc.get(name, 0.0) + seconds
        if self._open:
            self._open[-1][0] += seconds

    def reset(self) -> None:
        """Drop the accumulated (un-closed) phases without observing
        them: the trainer calls this at its FIRST step observation —
        where no inter-step interval exists yet — so the first step's
        jit compile (accumulated inside ``compute``) is never observed
        as if it were a normal step's phase split."""
        self._acc = dict.fromkeys(self.phases, 0.0)

    # -- per-step close ------------------------------------------------------
    def step_done(self, wall_dt: float, step: int | None = None) -> None:
        """Close the current step's ledger against its measured wall
        time (the trainer's inter-step interval): observe the phase
        histograms, update the coverage self-check, and emit the trace
        event when a capture is armed (or the throttle allows)."""
        if not self.enabled:
            return
        t_self = time.perf_counter()
        acc, self._acc = self._acc, dict.fromkeys(self.phases, 0.0)
        total = 0.0
        for p, v in acc.items():
            self._histogram.labels(phase=p).observe(v)
            self._totals[p] = self._totals.get(p, 0.0) + v
            self._cum[p] = self._cum.get(p, 0.0) + v
            total += v
        # time between steps is no part of the step: out of its wall
        # time and out of what the phases must account for
        idle = acc.get(self._idle_phase, 0.0) if self._idle_phase else 0.0
        wall_dt = max(0.0, wall_dt - idle)
        total -= idle
        self._steps += 1
        self._cum_wall += wall_dt
        self._totals_steps += 1
        self._totals_wall += wall_dt
        if wall_dt > 0:
            cover = min(1.0, total / wall_dt)
            self._cover_ema = (cover if self._cover_ema is None
                               else 0.9 * self._cover_ema + 0.1 * cover)
            if self._coverage_gauge is not None:
                self._coverage_gauge.set(self._cover_ema)
        now = time.monotonic()
        if now < self._capture_until:
            # capture window: one event PER STEP, exact per-phase split
            obs_trace.emit(self._event, dur=wall_dt,
                           # edl-lint: disable=clock — back-dating a TRACE
                           # ts to the span begin (merge convention: ts is
                           # begin), not deadline arithmetic
                           at=time.time() - wall_dt,
                           step=step, steps=1,
                           counters={p: round(v, 6) for p, v in acc.items()})
        elif now - self._last_emit >= _EMIT_EVERY_S:
            self.flush(now=now, step=step)
        # the ledger's own close-out cost (histogram observes, trace
        # emits) is real per-step overhead: charge it to the NEXT
        # step's hooks so the coverage self-check stays honest on
        # sub-millisecond steps
        self._acc[self._overhead_phase] += time.perf_counter() - t_self

    def flush(self, now: float | None = None, step: int | None = None
              ) -> None:
        """Emit the aggregated ``train/step_phases`` event for the
        window since the last emit (the coarse always-on history).
        Counters are the PER-STEP MEAN seconds by phase — the same
        unit the per-step capture events use, so both land on one
        comparable Perfetto counter track instead of window totals
        spiking ~1000x above step samples at capture boundaries;
        ``dur``/``steps`` keep the window totals."""
        if not self.enabled or not self._totals_steps:
            return
        n = self._totals_steps
        obs_trace.emit(self._event, dur=round(self._totals_wall, 6),
                       # edl-lint: disable=clock — back-dating a TRACE ts
                       # to the window begin, not deadline arithmetic
                       at=time.time() - self._totals_wall,
                       step=step, steps=n,
                       counters={p: round(v / n, 6)
                                 for p, v in self._totals.items()})
        self._totals = dict.fromkeys(self.phases, 0.0)
        self._totals_wall = 0.0
        self._totals_steps = 0
        self._last_emit = time.monotonic() if now is None else now

    def totals(self) -> dict:
        """Since construction, in one call: ``steps`` closed, their
        ``wall_s``, the ``coverage`` EMA, and exclusive ``phases``
        seconds by name.  Cumulative, so a reader differences two
        calls."""
        return {"steps": self._steps, "wall_s": self._cum_wall,
                "coverage": self._cover_ema, "phases": dict(self._cum)}

    # -- capture window (the CPU fallback of /profile) -----------------------
    def start_capture(self, duration_s: float) -> None:
        """Arm per-step trace emission for ``duration_s`` from now —
        the phase-ledger profile capture (:mod:`edl_tpu.obs.profile`
        uses it where ``jax.profiler`` is unavailable or too heavy)."""
        self._capture_until = time.monotonic() + max(0.0, float(duration_s))

    def capture_active(self) -> bool:
        return time.monotonic() < self._capture_until

    @property
    def coverage(self) -> float | None:
        """The coverage EMA (None before the first completed step)."""
        return self._cover_ema


# -- request stages ----------------------------------------------------------

# one ladder for every stage with tails: ratio 1.5 from 2 ms to 50 s (a
# tick is 9-50 ms, a long prompt's lane seconds, a queue at the knee
# tens of seconds), +Inf last.  Rounded to three figures: the edge is part of a
# key's NAME (``stage_<stage>_le_<edge>``), which a reader parses back
STAGE_EDGES = tuple(float(f"{0.002 * 1.5 ** i:.3g}") for i in range(26)) \
    + (math.inf,)


def _edge_name(edge: float) -> str:
    return "inf" if edge == math.inf else f"{edge:g}"


class RequestStageLedger:
    """Durations of the stages of a request's life, by stage and lane.

    ``stages`` maps each stage to the lanes its ``lane=`` may take (a
    stage ended where the lane is not known, or whose split nobody
    reads, has none).  ``tails`` maps the stages whose percentiles are
    read to their registry histogram: those also get the ladder's
    bucket counts in :meth:`totals`, the others a sum and a count.
    NOT thread-safe: the owner calls :meth:`observe` and :meth:`totals`
    under one lock (the histograms have their own)."""

    def __init__(self, stages: dict[str, tuple[str, ...]],
                 tails: dict | None = None):
        self._tails = dict(tails or {})
        # every key exists from construction: a reader that differences
        # two totals() only sees the keys of the first
        self._sums: dict[str, float] = {}
        for st, lanes in stages.items():
            for who in (st, *(f"{st}_{ln}" for ln in lanes)):
                self._sums[f"stage_{who}_sum_s"] = 0.0
                self._sums[f"stage_{who}_n"] = 0
        self._counts = {st: [0] * len(STAGE_EDGES) for st in self._tails}
        self._le = {st: [f"stage_{st}_le_{_edge_name(e)}"
                         for e in STAGE_EDGES] for st in self._tails}

    def observe(self, stage: str, seconds: float,
                lane: str | None = None) -> None:
        """One request spent ``seconds`` in ``stage`` (having taken
        ``lane``): sum and count, the lane's sum and count, and where
        the stage's tails are read its histogram and the ladder.  A
        stage or a lane it was not built with is a ``KeyError``."""
        seconds = max(0.0, float(seconds))
        # the lane first: an unknown one raises before anything moved
        for who in (stage,) if lane is None else (f"{stage}_{lane}", stage):
            self._sums[f"stage_{who}_sum_s"] += seconds
            self._sums[f"stage_{who}_n"] += 1
        hist = self._tails.get(stage)
        if hist is not None:
            hist.observe(seconds)
            # first edge >= seconds, as a Prometheus ``le`` bucket
            self._counts[stage][bisect_left(STAGE_EDGES, seconds)] += 1

    def totals(self) -> dict:
        """Since construction, flat and numeric: ``stage_<stage>_sum_s``
        / ``_n``, ``stage_<stage>_<lane>_sum_s`` / ``_n`` and, for a
        stage with tails, the CUMULATIVE bucket counts
        ``stage_<stage>_le_<edge>`` (the last, ``le_inf``, equals
        ``_n``).  A reader differences two calls key by key."""
        out = dict(self._sums)
        for stage, counts in self._counts.items():
            out.update(zip(self._le[stage], accumulate(counts)))
        return out
