"""CI smoke for streamed batch delivery: run the data-delivery
microbench (2 producer pods + 1 consumer over loopback) and gate it two
ways:

- **throughput**: the streamed pipeline (framed ``get_batch_stream``
  groups + multi-worker prefetch) must not lose to the legacy
  per-batch request/reply consumer.  The fetch ops carry a small
  injected per-dispatch wire delay (see ``delivery_microbench``) —
  loopback RTT is ~0 and would hide exactly the round-trip-per-batch
  cost the streamed transport removes; with it, the comparison is
  structural: the same work with ~8x fewer request round trips cannot
  be slower, so a loss here means the streamed path quietly demoted or
  the prefetcher collapsed — what this stage exists to catch.
- **exactly-once**: every run in the section (including the one that
  stops a producer's server mid-epoch) audits its raw span log — a
  drop or a duplicate fails the microbench itself, and this smoke
  re-asserts the counts on the artifact.

The absolute records/s land in the CI log for trend-eyeballing.
"""

import json
import os
import shutil
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from edl_tpu.data import DistributedReader, PodDataServer  # noqa: E402
from edl_tpu.data import distribute_reader as dr_mod  # noqa: E402
from edl_tpu.utils import faultinject  # noqa: E402


def delivery_microbench(n_files: int = 6, per_file: int = 240,
                        rec_bytes: int = 256, bs: int = 8, reps: int = 1,
                        step_ms: float = 2, rtt_ms: float = 2) -> dict:
    """Streamed batch-delivery microbench (ISSUE 11): 2 producer pods
    + 1 consumer over loopback, one full epoch drained four ways.
    Reported:

    - ``data_delivery_samples_s`` — records/s the consumer drains over
      the STREAMED path (framed ``get_batch_stream`` groups + the
      multi-worker prefetcher);
    - ``data_delivery_rpc_samples_s`` — the same epoch over the legacy
      one-batch-per-RPC path (what every old peer demotes to);
    - ``data_delivery_consumed_samples_s`` — streamed delivery feeding
      a consumer that "trains" for a fixed per-batch step time — the
      delivered-vs-consumed split, with
      ``data_delivery_consumed_stall_s`` saying how long the consumer
      actually waited on input (~0 = the prefetcher kept ahead);
    - ``data_delivery_pod_loss_samples_s`` — a streamed epoch with one
      producer's server stopped mid-epoch: the rebalance (dead-fetch
      timeouts, nack, requeue, re-production) priced in records/s;
    - every run is audited exactly-once (a drop or duplicate fails the
      section rather than reporting a corrupt-throughput number).

    Loopback RTT is ~0, which would hide exactly the cost the streamed
    transport removes (a request round trip per batch), so the batch
    FETCH ops carry an injected per-dispatch wire delay (``rtt_ms``,
    via the utils/faultinject harness) modeling a real pod network; every path pays the same
    per-dispatch price — per-batch pays it per batch, streamed per
    group — which is the structural difference being measured.
    """
    step_s, rtt_s = step_ms / 1e3, rtt_ms / 1e3

    data_dir = tempfile.mkdtemp(prefix="edl-delivery-")
    pad = "x" * rec_bytes
    for f in range(n_files):
        with open(os.path.join(data_dir, f"part-{f}.txt"), "w") as fh:
            fh.writelines(f"f{f}r{r}:{pad}\n" for r in range(per_file))
    files = sorted(os.path.join(data_dir, f) for f in os.listdir(data_dir))
    total = n_files * per_file

    def run_epoch(gen: str, stream: bool, legacy: bool = False,
                  kill: bool = False, consume_s: float = 0.0,
                  use_files: "list[str] | None" = None,
                  ) -> tuple[float, float]:
        """Drain one epoch; returns (records/s, consumer stall s).
        ``legacy=True`` shapes the consumer like the pre-ISSUE-11
        reader: one fetch worker, one batch per round trip, 4-meta
        lookahead — the honest "before" of the before/after."""
        epoch_files = files if use_files is None else use_files
        epoch_total = len(epoch_files) * per_file
        leader = PodDataServer("bench-consumer", is_leader=True)
        producers: list = []  # (pod_server, reader, thread)
        stall0 = dr_mod._PREFETCH_STALL.value
        spans: list = []
        try:
            for pid in ("bench-prod-a", "bench-prod-b"):
                srv = PodDataServer(pid)
                rd = DistributedReader(gen, pid, leader.endpoint, srv,
                                       batch_size=bs, stream=stream)
                rd.create(epoch_files)
                th = threading.Thread(target=rd._produce, daemon=True,
                                      name=f"bench-produce:{pid}")
                th.start()
                producers.append((srv, rd, th))
            # the consumer is consume-ONLY (its producer thread exits
            # at once): every batch crosses the wire, so the number
            # prices the DELIVERY pipeline, not local cache pops
            tuning = (dict(fetch_workers=1, meta_prefetch=4,
                           prefetch_depth=4) if legacy else
                      dict(meta_prefetch=16, prefetch_depth=48))
            consumer = DistributedReader(gen, "bench-consumer",
                                         leader.endpoint, leader,
                                         batch_size=bs, stream=stream,
                                         **tuning)
            consumer.create(epoch_files)
            consumer._stop_produce.set()
            got = 0
            killed = False
            t0 = time.perf_counter()
            for _bid, payload in consumer:
                spans.extend(payload["spans"])
                got += len(payload["records"])
                if consume_s:
                    time.sleep(consume_s)  # the simulated train step
                if kill and not killed and got >= epoch_total // 3:
                    srv_a, rd_a, _th_a = producers[0]
                    rd_a._stop_produce.set()
                    srv_a.stop()  # its batch cache goes dark mid-epoch
                    killed = True
            dt = time.perf_counter() - t0
            counts: dict = {}
            for f, b, e in spans:
                for r in range(b, e):
                    counts[(f, r)] = counts.get((f, r), 0) + 1
            dup = sum(1 for c in counts.values() if c > 1)
            if len(counts) != epoch_total or dup:
                raise RuntimeError(
                    f"delivery audit failed ({gen}): {len(counts)} "
                    f"distinct records != {epoch_total}, {dup} duplicated")
            return epoch_total / dt, dr_mod._PREFETCH_STALL.value - stall0
        finally:
            for _srv, rd, _th in producers:
                rd._stop_produce.set()
            for _srv, rd, th in producers:
                th.join(timeout=10)
                rd.close(deadline=2.0)
            for srv, _rd, _th in producers:
                try:
                    srv.stop()
                except Exception:  # noqa: BLE001 — teardown
                    pass
            leader.stop()

    stream_rate = stall = rpc_rate = 0.0
    try:
        if rtt_s > 0:
            faultinject.configure(
                f"client:get_batch_data:delay:{rtt_s};"
                f"client:get_batch_stream:delay:{rtt_s}")
        for rep in range(max(1, reps)):
            rate, s = run_epoch(f"deliver-stream-r{rep}@e0", stream=True)
            if rate > stream_rate:
                stream_rate, stall = rate, s
            rpc_rate = max(rpc_rate,
                           run_epoch(f"deliver-rpc-r{rep}@e0", stream=False,
                                     legacy=True)[0])
        consumed_rate, consumed_stall = run_epoch(
            "deliver-consumed@e0", stream=True, consume_s=step_s)
        # a quarter-size epoch: the rebalance price (dead-fetch
        # timeouts, nack, requeue, re-production) dominates its wall
        # time, and the full-epoch runs above already price steady state
        loss_rate, _ = run_epoch("deliver-loss@e0", stream=True, kill=True,
                                 use_files=files[:max(2, n_files // 3)])
    finally:
        # restore whatever fault spec the process came with
        seed = os.environ.get("EDL_TPU_FAULTS_SEED")
        faultinject.configure(os.environ.get("EDL_TPU_FAULTS"),
                              int(seed) if seed else None)
        shutil.rmtree(data_dir, ignore_errors=True)
    return {
        "data_delivery_samples_s": round(stream_rate, 1),
        "data_delivery_rpc_samples_s": round(rpc_rate, 1),
        "data_delivery_stream_ratio": round(
            stream_rate / max(rpc_rate, 1e-9), 2),
        "data_delivery_stall_s": round(stall, 3),
        "data_delivery_consumed_samples_s": round(consumed_rate, 1),
        "data_delivery_consumed_stall_s": round(consumed_stall, 3),
        "data_delivery_pod_loss_samples_s": round(loss_rate, 1),
        "data_delivery_records": total,
    }


def main() -> int:
    # small-but-real epoch: ~180 batches, best-of-2 to damp CI noise
    r = delivery_microbench(n_files=6, per_file=240, reps=2)
    print(json.dumps(r))
    streamed = r["data_delivery_samples_s"]
    per_batch = r["data_delivery_rpc_samples_s"]
    print(f"data throughput smoke: streamed={streamed} rec/s, "
          f"per-batch={per_batch} rec/s "
          f"({r['data_delivery_stream_ratio']:.2f}x), consumed="
          f"{r['data_delivery_consumed_samples_s']} rec/s "
          f"(stall {r['data_delivery_consumed_stall_s']}s), "
          f"pod-loss={r['data_delivery_pod_loss_samples_s']} rec/s")
    if streamed < per_batch:
        print("FAIL: streamed delivery slower than the per-batch "
              "request/reply baseline", file=sys.stderr)
        return 1
    # the microbench audits every epoch internally (and raises on failure);
    # assert the artifact agrees so a silent audit regression cannot
    # pass this stage
    if r.get("data_delivery_records", 0) <= 0:
        print("FAIL: delivery microbench reported no audited records",
              file=sys.stderr)
        return 1
    print("data throughput smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
