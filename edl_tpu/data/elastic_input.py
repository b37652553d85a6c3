"""ElasticInput: the data service wired into collective training.

This is the integration the reference never finished (SURVEY.md §2.4:
distribute_reader.py was broken/WIP; examples sharded files statically
per rank).  One object per trainer process turns the span-aware work
queue (data_server.py) into **fixed-size, collectively-agreed, masked
batches** safe to feed a jitted multi-host train step:

- per epoch, every pod registers its batch cache in the reader
  registry and waits until the reader set equals the cluster pod set
  (reference reader.py:70-99), so all processes enter together;
- records stream in via :class:`DistributedReader` (work-stealing, so
  pods consume *different* amounts) and are re-chunked into exactly
  ``batch_size``-record host batches;
- every step runs a tiny **has-next agreement** across processes
  (allgather of one flag): while ANY pod still has records, every pod
  steps — pods with a short/empty buffer pad with zeros and a 0 mask.
  The loss must be mask-weighted (``sum(loss*mask)/sum(mask)``), which
  makes the ragged end of an epoch *counted* instead of dropped: every
  record trains exactly once, and collective step counts always match
  (the raggedness problem the reference's batch-id rebalance barrier
  tried and failed to solve, data_server.py:171-224).  Caveat: models
  with cross-example batch statistics (BatchNorm) still see padded
  rows inside their statistics — gate the running-stat update on
  ``mask.min() > 0`` (see train_resnet.py) or prefer per-example
  norms (GroupNorm/LayerNorm) for bitwise exactness;
- records are marked into the job's :class:`DataCheckpoint` only when
  their batch is actually yielded to the train loop, so a mid-epoch
  Orbax save captures exactly the trained-so-far set and stop-resume
  (any world size) resumes the epoch exactly once.
"""

from __future__ import annotations

import time
from typing import Callable, Iterator

import numpy as np

from edl_tpu.cluster.cluster import Cluster
from edl_tpu.cluster.state import DataCheckpoint
from edl_tpu.data import registry
from edl_tpu.data.data_server import PodDataServer
from edl_tpu.data.dataset import FileSplitter
from edl_tpu.data.distribute_reader import DistributedReader
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.utils import constants
from edl_tpu.utils.exceptions import EdlDataError
from edl_tpu.utils.logger import get_logger

logger = get_logger(__name__)

# assemble(records) -> {"name": np.ndarray (B', ...)} for B' <= batch_size
Assemble = Callable[[list], dict]

# batches carry their consumed record spans under this key; the trainer
# pops it and marks the DataCheckpoint when the batch is actually trained
SPANS_KEY = constants.DATA_SPANS_KEY

_H2D_WAIT = obs_metrics.counter(
    "edl_data_h2d_wait_seconds_total",
    "Seconds the consumer waited on the staged device transfer in "
    "device_put_stream (H2D not hidden behind compute; ~0 when the "
    "overlap works)")


def _allgather_flag(flag: int) -> np.ndarray:
    from edl_tpu.parallel.sharding import allgather_flag
    return allgather_flag(flag)


def sync_checkpoint(checkpoint: DataCheckpoint) -> None:
    """Merge every process's consumed spans into ``checkpoint`` in place.

    The Orbax JSON sidecar is written by the primary host only, but each
    process marks only the records IT trained — without this merge a
    mid-epoch checkpoint would lose every other host's spans and a
    resumed job would re-train them.  Must be called at the same step on
    every process (the trainer calls it right before each save; steps
    are collective, so save points always align)."""
    import jax

    if jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    local = np.asarray([[r.file_idx, r.begin, r.end]
                        for r in checkpoint.processed],
                       np.int32).reshape(-1, 3)
    counts = np.asarray(multihost_utils.process_allgather(
        np.asarray(len(local), np.int32)))
    cap = int(counts.max())
    if cap == 0:
        return
    padded = np.zeros((cap, 3), np.int32)
    padded[:len(local)] = local
    gathered = np.asarray(multihost_utils.process_allgather(padded))
    from edl_tpu.cluster.state import ProcessedRange
    from edl_tpu.utils.spans import merge_span

    per_file: dict[int, list[list[int]]] = {}
    for p in range(gathered.shape[0]):
        for i in range(int(counts[p])):
            fi, b, e = (int(x) for x in gathered[p, i])
            merge_span(per_file.setdefault(fi, []), b, e)
    checkpoint.processed = [ProcessedRange(fi, b, e)
                            for fi in sorted(per_file)
                            for b, e in per_file[fi]]


def device_put_stream(batches: "Iterator[dict]", put: Callable[[dict], object],
                      ) -> "Iterator[tuple[object, list]]":
    """Double-buffered device staging: run ``put`` (``jax.device_put``,
    ``shard_host_batch``, ...) on batch k+1 in a background thread
    while the caller consumes batch k, so H2D of the next batch
    overlaps decode/compute on the current one.

    Yields ``(device_batch, spans)`` with the ``SPANS_KEY`` metadata
    split out BEFORE the put: record spans must stay host-side, and
    they must be marked by the CONSUMER at train time, never by the
    staging thread (a prefetching stage marking spans would let a
    mid-epoch checkpoint claim records one batch ahead of what
    actually trained).  Depth is fixed at one batch so the collective
    order of the source iterator's internals (the has-next agreement)
    stays identical on every process — the same contract as the
    trainer's ``_sharded_stream``."""
    from concurrent.futures import ThreadPoolExecutor

    def split(batch):
        spans = None
        if isinstance(batch, dict) and SPANS_KEY in batch:
            batch = dict(batch)
            spans = batch.pop(SPANS_KEY)
        return batch, spans

    def staged_result(staged):
        t0 = time.perf_counter()
        out = staged[0].result()
        _H2D_WAIT.inc(time.perf_counter() - t0)
        return out, staged[1]

    with ThreadPoolExecutor(1, thread_name_prefix="h2d-stage") as pool:
        staged = None
        for batch in batches:
            host, spans = split(batch)
            nxt = (pool.submit(put, host), spans)
            if staged is not None:
                yield staged_result(staged)
            staged = nxt
        if staged is not None:
            yield staged_result(staged)


class ElasticInput:
    """Lives for the whole trainer process; ``epoch()`` yields one
    epoch's batches.  ``assemble`` builds host-batch arrays from raw
    records; short/empty batches are zero-padded and masked.

    The underlying :class:`DistributedReader` reads its prefetch
    tuning (fetch workers, queue bound, metas per leader round trip,
    streamed vs per-batch transport) from the
    ``EDL_TPU_DATA_PREFETCH_*`` env knobs, so the launcher path picks
    up operator tuning with no code change here."""

    def __init__(self, store, job_id: str, pod_id: str, reader_base: str,
                 files: list[str], batch_size: int, splitter: FileSplitter,
                 assemble: Assemble, distributed: bool = False,
                 cache_cap: int = 256):
        self._store = store
        self._job_id = job_id
        self._pod_id = pod_id
        self._base = reader_base
        self._files = sorted(files)
        self._bs = batch_size
        self._splitter = splitter
        self._assemble = assemble
        self._distributed = distributed
        self.server = PodDataServer(pod_id, cache_cap=cache_cap)

    def _leader_resolver(self):
        """Resolver handed to the reader's resilient client: the leader
        endpoint is re-read from the CURRENT cluster record on every
        failover, so a blipped leader that came back — or a successor
        hosting the rebuilt DataService — is found without restarting
        the epoch."""
        def resolve() -> str:
            cluster = Cluster.load_from_store(self._store, self._job_id)
            if cluster is None or cluster.leader is None:
                raise EdlDataError("cluster has no pods")
            return cluster.leader.endpoint
        return resolve

    def epoch(self, epoch: int, checkpoint: DataCheckpoint,
              device_put: "Callable[[dict], object] | None" = None,
              ) -> Iterator[dict]:
        """Yield masked host batches for one epoch.  The generation key
        is ``base@e<epoch>@<stage>`` — a new cluster stage (elastic
        resize) or epoch makes a fresh generation, seeded from
        ``checkpoint`` (the restored mid-epoch spans on resume).

        With ``device_put`` set, batches ride :func:`device_put_stream`
        and the iterator yields ``(device_batch, spans)`` pairs instead
        of raw host dicts: batch k+1's H2D overlaps the caller's
        consumption of batch k (callers that already stage — the
        trainer's ``_sharded_stream`` — leave it None)."""
        cluster = Cluster.load_from_store(self._store, self._job_id)
        if cluster is None:
            raise EdlDataError("no cluster in store; is the launcher up?")
        name = f"{self._base}@e{epoch}@{cluster.stage[:8]}"
        checkpoint.reader_name = name
        reg = registry.register_reader(self._store, self._job_id, name,
                                       self._pod_id, self.server.endpoint)
        reader = None
        try:
            registry.wait_dist_readers(self._store, self._job_id, name,
                                       cluster.pod_ids())
            reader = DistributedReader(
                name, self._pod_id, self._leader_resolver(),
                self.server, batch_size=self._bs, splitter=self._splitter,
                checkpoint=checkpoint, mark_on_yield=False)
            reader.create(self._files)
            if device_put is None:
                yield from self._batches(reader)
            else:
                yield from device_put_stream(self._batches(reader),
                                             device_put)
        finally:
            if reader is not None:
                reader.close()
            reg.stop()

    # -- the re-chunk + agreement loop ---------------------------------------
    def _batches(self, reader: DistributedReader) -> Iterator[dict]:
        buf: list[tuple[object, int, int]] = []  # (record, file_idx, record_no)
        it = iter(reader)
        exhausted = False
        while True:
            while len(buf) < self._bs and not exhausted:
                try:
                    _bid, payload = next(it)
                except StopIteration:
                    exhausted = True
                    break
                records = payload["records"]
                coords = [(fi, no) for fi, b, e in payload["spans"]
                          for no in range(b, e)]
                assert len(coords) == len(records), \
                    f"spans cover {len(coords)} records, got {len(records)}"
                buf.extend((r, fi, no)
                           for r, (fi, no) in zip(records, coords))
            has = int(bool(buf))
            if self._distributed:
                flags = _allgather_flag(has)
                if not flags.any():
                    return
            elif not has:
                return
            take, buf = buf[:self._bs], buf[self._bs:]
            batch = self._assemble([r for r, _fi, _no in take])
            n = len(take)
            pad = self._bs - n
            if pad:
                batch = {k: np.concatenate(
                    [v, np.zeros((pad,) + v.shape[1:], v.dtype)])
                    for k, v in batch.items()}
            batch["mask"] = np.concatenate(
                [np.ones(n, np.float32), np.zeros(pad, np.float32)])
            # the batch CARRIES its record spans (contiguous runs); the
            # consumer marks them into the DataCheckpoint at the moment
            # it actually trains the batch — marking here would let a
            # prefetching trainer checkpoint spans one batch ahead of
            # what was trained, and a resume would skip untrained records
            runs: list[list[int]] = []
            for _r, fi, no in take:
                if runs and runs[-1][0] == fi and runs[-1][2] == no:
                    runs[-1][2] = no + 1
                else:
                    runs.append([fi, no, no + 1])
            batch[SPANS_KEY] = runs
            yield batch

    def stop(self) -> None:
        self.server.stop()
