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

**The program-scoped sibling** (:class:`ProgramBuildLedger`,
process-wide as :data:`PROGRAM_BUILDS`).  A step has phases, a request
has stages, a PROGRAM has a BUILD: ``trace`` (Python to a jaxpr),
``lower`` (jaxpr to MLIR: a ``pallas_call`` is lowered here, once a
call site), ``compile`` (XLA on a persistent-cache miss, reading and
deserialising on a hit) and ``run`` (the first execution).  The clock
of the first three is JAX's own: ``jax.monitoring`` calls
:meth:`ProgramBuildLedger.on_duration` / :meth:`~ProgramBuildLedger.on_event`
on the compiling thread (``utils/compile_cache.enable_compile_cache``
registers them, once), and each event is booked to the span that
thread has open, ``with PROGRAM_BUILDS.build(component, family)``, or
to component ``other`` under the program's own name where none is.
Nothing fires once a program is built: the steady state pays nothing.
"""

from __future__ import annotations

import math
import re
import sys
import threading
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


# -- program builds ----------------------------------------------------------

BUILD_STAGES = ("trace", "lower", "compile", "run")
# jax.monitoring's duration events, by the stage each is (jax 0.9:
# jax/_src/dispatch.py).  ``backend_compile_duration`` spans the
# persistent cache's lookup too, so on a hit it IS the retrieval.
_JAX_STAGE = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
# jax counts ``cache_misses`` only where it then WRITES the entry (a
# compile under its size and time thresholds is never kept): a request
# that did not hit is the miss here
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
UNLABELLED = "other"
_ROW_FIELDS = ("builds", *(f"{s}_s" for s in BUILD_STAGES), "cache_hits",
               "cache_misses")
# events a thread keeps to find what a later, enclosing one contains
_LOOSE_EVENTS = 4096
_WRAPPED_NAME = re.compile(r"^\w+\((.*)\)$")

_BUILD_SECONDS = obs_metrics.counter(
    "edl_program_build_seconds_total",
    "Seconds this process spent building its programs, by component "
    "(engine / kv / train / other = no span owned the compile) and stage: "
    "trace / lower / compile (XLA on a persistent-cache miss, retrieval "
    "on a hit) / run (a built program's first execution, state placement)",
    ("component", "stage"))
_BUILDS_TOTAL = obs_metrics.counter(
    "edl_program_builds_total",
    "Programs this process asked XLA for, by component and what the "
    "persistent compile cache said: hit / miss / none (not asked)",
    ("component", "cache"))


class _BuildFrame:
    """One open span of one thread; or, with no ``row``, what a thread
    with no span open has gathered since its last compile."""

    __slots__ = ("row", "key", "inner_s", "stage_s", "events", "programs",
                 "hits", "requests", "retrieve_s")

    def __init__(self, row: tuple, key):
        self.row, self.key = row, key
        self.inner_s = 0.0          # spans closed inside this one
        self.stage_s = dict.fromkeys(BUILD_STAGES[:3], 0.0)
        self.events: list[tuple[float, float]] = []
        self.programs = self.hits = self.requests = 0
        self.retrieve_s = 0.0


class _FirstCall:
    """A jitted program until its first call has run: that call runs
    inside the program's build span and waits for its outputs, then the
    place that held this object (``holder[name]`` or ``holder.name``,
    if it still does) holds the bare program again.  Whoever kept a
    reference meanwhile gets a plain forward.  Everything else
    (``lower``, ``trace``) is the program's own."""

    def __init__(self, ledger, fn, component, family, key, holder, name):
        self.fn = fn
        self._span = (ledger, component, family, key)
        self._held = (holder, name)

    def __call__(self, *args, **kwargs):
        if self._span is None:
            return self.fn(*args, **kwargs)
        ledger, component, family, key = self._span
        with ledger.build(component, family, key=key):
            out = self.fn(*args, **kwargs)
            jax = sys.modules.get("jax")
            if jax is not None:
                jax.block_until_ready(out)
        (holder, name), self._span, self._held = self._held, None, None
        if isinstance(holder, dict):
            if holder.get(name) is self:
                holder[name] = self.fn
        elif getattr(holder, name, None) is self:
            setattr(holder, name, self.fn)
        return out

    def __getattr__(self, name):
        return getattr(self.fn, name)


class ProgramBuildLedger:
    """Where a start goes: cumulative rows by (kind, component, family)
    of programs built, seconds by stage and persistent-cache hits and
    misses.  ``kind`` is ``build`` (a program: traced, lowered,
    compiled or retrieved, run once) or ``setup`` (state placed:
    weights cast, caches and pools allocated, a restore; the programs
    that takes are booked inside it).

    Thread-safe.  The open spans are held per THREAD (a background
    compile is booked to its own label, not to whatever the loop has
    open) and gather their events without a lock; rows and the
    per-thread sums are written under one lock when a span closes (or
    a compile no span owns ends), so :meth:`totals` holds closed spans
    only."""

    def __init__(self):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._rows: dict[tuple, dict] = {}
        self._by_thread: dict[int, list] = {}

    # -- spans ---------------------------------------------------------------
    @contextmanager
    def build(self, component: str, family: str, key=None,
              kind: str = "build"):
        """Open ``<kind>/<component>/<family>`` on this thread: a
        profiler annotation (in a ``/profile`` capture of a starting
        process it sits on the device's clock) and, when it closes, a
        trace event with ``key``, the seconds by stage and ``cache``
        (hit / miss / none).  The block's wall seconds less the JAX
        stages booked inside it are its ``run`` stage.  A ``build``
        span in which no program was compiled or retrieved (a first
        call that found its program built) books nothing; a ``setup``
        span always books.  A span inside another is deducted from it."""
        frames = getattr(self._tls, "frames", None)
        if frames is None:
            frames = self._tls.frames = []
        frame = _BuildFrame((kind, component, family), key)
        frames.append(frame)
        t_wall = time.time()
        t0 = time.perf_counter()
        try:
            with obs_trace.annotation(f"{kind}/{component}/{family}"):
                yield
        finally:
            dt = time.perf_counter() - t0
            frames.pop()
            if frames:
                frames[-1].inner_s += dt
            if frame.programs or kind != "build":
                self._close(frame, dt, t_wall)

    def setup(self, component: str, family: str = "state", key=None):
        """``setup/<component>/<family>``: state placed, not a program."""
        return self.build(component, family, key=key, kind="setup")

    def first_call(self, fn, component: str, family: str, key, holder, name):
        """``fn`` wrapped so that its FIRST call is its build span
        (:class:`_FirstCall`); put the result where ``holder[name]`` /
        ``holder.name`` says, and that place holds ``fn`` itself from
        the first call on: nothing stays on the caller's path."""
        return _FirstCall(self, fn, component, family, key, holder, name)

    def _close(self, frame: _BuildFrame, dt: float, t_wall: float) -> None:
        own = max(0.0, dt - frame.inner_s)
        run = max(0.0, own - sum(frame.stage_s.values()))
        self._book(frame.row, frame, run)
        if obs_trace.active():
            cache = ("none" if not frame.requests else
                     "hit" if frame.hits == frame.requests else "miss")
            obs_trace.emit(
                "/".join(frame.row), dur=dt, at=t_wall, key=repr(frame.key),
                programs=frame.programs, cache=cache, run_s=round(run, 6),
                retrieve_s=round(frame.retrieve_s, 6),
                **{f"{s}_s": round(v, 6) for s, v in frame.stage_s.items()})

    def _book(self, row: tuple, frame: _BuildFrame, run: float) -> None:
        """What ``frame`` gathered, into ``row``, this thread's sums and
        the registry: one lock a span (or a compile no span owns), not
        one an event."""
        misses = frame.requests - frame.hits
        with self._lock:
            got = self._rows.get(row)
            if got is None:
                got = self._rows[row] = dict.fromkeys(_ROW_FIELDS, 0.0)
            for stage, secs in frame.stage_s.items():
                got[f"{stage}_s"] += secs
            got["run_s"] += run
            got["builds"] += frame.programs
            got["cache_hits"] += frame.hits
            got["cache_misses"] += misses
            sums = getattr(self._tls, "sums", None)
            if sums is None:
                # a new thread starts from nothing, whatever a dead one
                # with its ident left
                sums = self._tls.sums = [0, 0.0]
                self._by_thread[threading.get_ident()] = sums
            sums[0] += frame.programs
            sums[1] += run + sum(frame.stage_s.values())
        component = row[1]
        for stage, secs in (*frame.stage_s.items(), ("run", run)):
            if secs:
                _BUILD_SECONDS.labels(component, stage).inc(secs)
        for cache, n in (("hit", frame.hits), ("miss", misses),
                         ("none", frame.programs - frame.requests)):
            if n:
                _BUILDS_TOTAL.labels(component, cache).inc(n)

    # -- jax.monitoring's side -----------------------------------------------
    def on_duration(self, event: str, seconds: float, **kw) -> None:
        """A ``jax.monitoring`` duration, on the thread that compiled:
        into the span that thread has open, booked when it closes; with
        none open, gathered until the program's compile event and
        booked under ``other`` and the compiled function's name.  The
        events nest (a trace contains its callees' traces, an eager
        constant compiles inside a trace) and each reports its whole
        length at its end: what a later event contains is deducted
        from it, so the stages add up to no more than the wall."""
        tls = self._tls
        frames = getattr(tls, "frames", None)
        if frames:
            frame = frames[-1]
        else:
            frame = getattr(tls, "loose", None)
            if frame is None:
                frame = tls.loose = _BuildFrame(None, None)
        stage = _JAX_STAGE.get(event)
        if stage is None:
            if event == _CACHE_RETRIEVAL:
                frame.retrieve_s += seconds
            return
        events = frame.events
        start = time.perf_counter() - seconds
        inner = 0.0
        while events and events[-1][0] >= start:
            inner += events.pop()[1]
        events.append((start, seconds))
        frame.stage_s[stage] += max(0.0, seconds - inner)
        if stage != "compile":
            return
        # what the cache said of THIS program: a request since the last
        # compile on this thread, and whether it hit
        req, tls.request = getattr(tls, "request", None), None
        frame.programs += 1
        frame.requests += req is not None
        frame.hits += req == "hit"
        if frame.row is None:
            name = str(kw.get("fun_name", "?"))
            wrapped = _WRAPPED_NAME.match(name)     # jit(f) is f's
            self._book(("build", UNLABELLED,
                        wrapped.group(1) if wrapped else name), frame, 0.0)
            # the events stay: a later one may contain them
            del events[:-_LOOSE_EVENTS]
            tls.loose = fresh = _BuildFrame(None, None)
            fresh.events = events

    def on_event(self, event: str, **_kw) -> None:
        """A ``jax.monitoring`` event: the persistent cache was asked
        for the program this thread is compiling, and it hit."""
        if event == _CACHE_REQUEST:
            self._tls.request = "miss"
        elif event == _CACHE_HIT:
            self._tls.request = "hit"

    # -- reading -------------------------------------------------------------
    def totals(self) -> dict:
        """Since the process started, flat and numeric:
        ``<kind>/<component>/<family>/<field>`` with the fields
        ``builds`` (programs XLA was asked for), ``trace_s`` /
        ``lower_s`` / ``compile_s`` / ``run_s`` and ``cache_hits`` /
        ``cache_misses``.  Cumulative: a reader differences two calls,
        or takes one at the end of a set-up."""
        with self._lock:
            return {"/".join((*row, f)): v for row, got in self._rows.items()
                    for f, v in got.items()}

    def thread_totals(self, ident: int) -> tuple[int, float]:
        """``(programs, seconds)`` that thread ``ident`` has built
        since the process started, spans and unlabelled compiles alike."""
        with self._lock:
            n, s = self._by_thread.get(ident, (0, 0.0))
        return int(n), float(s)


PROGRAM_BUILDS = ProgramBuildLedger()
