"""StudentFeed: stream a DistillReader while publishing a durable
backlog signal the DistillAutoscaler converts into teacher count.

The backlog is the student's own accounting — rows handed to the
predict pool minus rows received back — published two ways every
``EDL_TPU_DISTILL_BACKLOG_PERIOD`` seconds:

- a durable per-student record (``cluster/scale.py save_backlog``,
  key ``scale/backlog/<student>``) the controller's DistillAutoscaler
  sums across students; the record is timestamped and judged against
  ``EDL_TPU_DEMAND_TTL`` like demand records, so a dead student's last
  backlog decays instead of pinning teachers scaled out;
- ``edl_distill_*`` gauges/counters on the process registry, so the
  obs aggregator's merged page and ``/healthz`` distill block carry
  the same numbers.

The publisher is a THREAD, not an iteration hook: backlog grows
exactly while the student loop is blocked inside the pool, which is
when an inline hook would never run.
"""

from __future__ import annotations

import os
import threading
import time
import uuid

from edl_tpu.cluster import scale
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.utils import constants
from edl_tpu.utils.logger import get_logger
from edl_tpu.utils.network import local_ip

logger = get_logger(__name__)

_BACKLOG_ROWS_G = obs_metrics.gauge(
    "edl_distill_backlog_rows",
    "Rows this student has queued for teacher inference", ("job",))
_BACKLOG_S_G = obs_metrics.gauge(
    "edl_distill_backlog_seconds",
    "Estimated seconds of queued work at the observed teacher rate",
    ("job",))
_STUDENT_ROWS_TOTAL = obs_metrics.counter(
    "edl_distill_student_rows_total",
    "Teacher-annotated rows this student has consumed", ("job",))
_STUDENT_ROWS_S_G = obs_metrics.gauge(
    "edl_distill_student_rows_s",
    "Observed teacher throughput from the student side (EMA rows/s)",
    ("job",))


class StudentFeed:
    """Iterate ``reader`` (a configured DistillReader) while publishing
    the backlog signal for ``job_id`` (the TEACHER fleet's job).

    Usage::

        feed = StudentFeed(store, "teachers", reader)
        for batch in feed:
            ...

    ``submitted_rows``/``consumed_rows`` are exposed for tests.  The feed counts rows as
    they stream INTO the pool (the wrapped input generator) and OUT of
    it (yielded batches) — the difference is the backlog.
    """

    def __init__(self, store, job_id: str, reader,
                 student_id: str | None = None,
                 period: float | None = None,
                 batch_rows=None):
        self._store = store
        self.job_id = job_id
        self._reader = reader
        self.student_id = (student_id or
                           f"{local_ip()}-{os.getpid()}-{uuid.uuid4().hex[:6]}")
        self._period = (constants.DISTILL_BACKLOG_PERIOD if period is None
                        else float(period))
        # how many rows a yielded batch carries; default: len of the
        # first field (sample-list batches are tuples of stacked arrays)
        self._batch_rows = batch_rows or (lambda b: len(b[0]))
        self._lock = threading.Lock()
        self.submitted_rows = 0
        self.consumed_rows = 0
        self._rate_ema = 0.0            # rows/s the teachers deliver
        self._last_pub_rows = 0
        self._last_pub_t: float | None = None
        self._halt = threading.Event()
        self._thread: threading.Thread | None = None
        self._wrap_input()

    # -- input/output accounting ---------------------------------------------
    def _wrap_input(self) -> None:
        """Count rows as the pool pulls them from the user generator.
        Works for every reader mode: sample yields one row, sample_list
        a list of rows, batch a tuple of stacked columns."""
        inner, mode = self._reader._gen, self._reader._mode
        if inner is None:
            raise RuntimeError("reader has no input generator configured")

        def counted():
            for item in inner():
                if mode == "sample":
                    n = 1
                elif mode == "sample_list":
                    n = len(item)
                else:
                    n = len(item[0])
                with self._lock:
                    self.submitted_rows += n
                yield item
        self._reader._gen = counted

    def __iter__(self):
        self._start()
        try:
            for batch in self._reader():
                n = int(self._batch_rows(batch))
                with self._lock:
                    self.consumed_rows += n
                _STUDENT_ROWS_TOTAL.labels(job=self.job_id).inc(n)
                yield batch
        finally:
            self.stop()

    def __call__(self):
        return iter(self)

    # -- the backlog signal --------------------------------------------------
    def backlog_rows(self) -> int:
        with self._lock:
            return max(0, self.submitted_rows - self.consumed_rows)

    def observed_rows_per_s(self) -> float:
        with self._lock:
            return self._rate_ema

    def _publish_once(self, now: float | None = None) -> None:
        now = time.monotonic() if now is None else now
        with self._lock:
            queued = max(0, self.submitted_rows - self.consumed_rows)
            consumed = self.consumed_rows
            if self._last_pub_t is not None:
                dt = max(1e-6, now - self._last_pub_t)
                inst = (consumed - self._last_pub_rows) / dt
                # EMA so one idle publish window doesn't read as a dead
                # fleet; alpha 0.5 tracks scale-out within ~2 periods
                self._rate_ema = (inst if self._rate_ema == 0.0
                                  else 0.5 * self._rate_ema + 0.5 * inst)
            self._last_pub_rows = consumed
            self._last_pub_t = now
            rate = self._rate_ema
        _BACKLOG_ROWS_G.labels(job=self.job_id).set(queued)
        _STUDENT_ROWS_S_G.labels(job=self.job_id).set(round(rate, 3))
        # no observed rate yet (startup): read queued rows as seconds —
        # a conservative 1 row/s floor, so a backlog that exists before
        # any teacher answered still registers instead of reading 0
        _BACKLOG_S_G.labels(job=self.job_id).set(
            round(queued / rate, 3) if rate > 0 else float(queued))
        try:
            scale.save_backlog(self._store, self.job_id, self.student_id,
                               queued, rate)
        except Exception as e:  # noqa: BLE001 — a store blip skips one beat
            logger.warning("backlog record publish failed: %s", e)

    def _run(self) -> None:
        while not self._halt.wait(self._period):
            self._publish_once()

    def _start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, daemon=True,
                name=f"student-backlog:{self.student_id[:12]}")
            self._thread.start()

    def stop(self) -> None:
        self._halt.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        _BACKLOG_ROWS_G.labels(job=self.job_id).set(0)
        _BACKLOG_S_G.labels(job=self.job_id).set(0)
        try:
            scale.clear_backlog(self._store, self.job_id, self.student_id)
        except Exception as e:  # noqa: BLE001 — the TTL freshness rule
            logger.debug("backlog record clear failed (%s); the "
                         "freshness TTL decays it", e)
