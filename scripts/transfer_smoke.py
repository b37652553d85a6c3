"""CI smoke for the streaming data plane: run the transfer microbench
(loopback, small payload, subprocess holders) and assert the
pipelined/striped paths did not regress below the serial baseline.

Small-payload loopback numbers are noisy (scheduler, shared CI hosts),
so the gate compares the BEST of the new paths against serial —
structurally, pipelining the same work can't be slower than
serializing it, so a loss here means a protocol-level regression
(e.g. the window collapsed to 1 or streaming quietly fell back),
which is exactly what this stage exists to catch.  The absolute
bandwidth numbers go to the CI log for trend-eyeballing.
"""

import functools
import json
import os
import subprocess
import sys
import time
import zlib

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from edl_tpu.rpc import chunks, transfer  # noqa: E402
from edl_tpu.rpc.client import RpcChannelPool, RpcClient  # noqa: E402
from edl_tpu.utils import constants  # noqa: E402

_TRANSFER_HOLDER_SRC = """
import sys, zlib
import numpy as np
from edl_tpu.memstate.service import StateCacheService
from edl_tpu.rpc.server import RpcServer
mb = int(sys.argv[1])
data = np.random.default_rng(0).bytes(mb << 20)
svc = StateCacheService(None, "xfer", sys.argv[2])
svc.cache_put_chunk("owner", 1, "blob", 0, data, True)
svc.cache_commit("owner", 1, manifest={
    "blob": {"crc": zlib.crc32(data), "nbytes": len(data),
             "dtype": "uint8", "shape": [len(data)],
             "index": [[0, len(data)]], "gshape": [len(data)],
             "leaf": "blob"}})
srv = RpcServer("127.0.0.1", 0)
srv.register_instance(svc)
srv.start()
print(srv.port, flush=True)
sys.stdin.read()  # serve until the parent closes our stdin
"""


def transfer_microbench(mb: int = 64,
                        chunk: int = constants.MEMSTATE_CHUNK_BYTES,
                        window: int = constants.TRANSFER_WINDOW,
                        reps: int = 3) -> dict:
    """Peer-transfer data-plane microbench: the same blob fetched from
    loopback StateCacheService holders three ways — serial (one chunk
    per round trip on one connection, the pre-streaming baseline),
    pipelined (a window of chunk requests in flight on one
    connection), and striped (byte ranges split across TWO holders,
    server-push streaming, CRC overlapped with the fetch) — reported
    as MiB/s.  The holders run as SUBPROCESSES, like the real thing
    (peer launchers): an in-process server would share the client's
    GIL and understate every parallel path.  Loopback understates LAN
    RTT, so the pipelining win here is a lower bound on the real one.
    Every byte is CRC-verified against the manifest so a
    wrong-but-fast path can't win."""
    data = np.random.default_rng(0).bytes(mb << 20)
    crc = zlib.crc32(data)

    procs, pools = [], []
    try:
        for pid in ("xfer-a", "xfer-b"):
            p = subprocess.Popen(
                [sys.executable, "-c", _TRANSFER_HOLDER_SRC, str(mb), pid],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            procs.append(p)
        ports = [int(p.stdout.readline()) for p in procs]
        pools = [RpcChannelPool(f"127.0.0.1:{port}") for port in ports]

        def mib_s(seconds: float) -> float:
            return round(len(data) / (1 << 20) / max(seconds, 1e-9), 1)

        def time_best(fn) -> float:
            """Warmup (connections, page cache) + best-of-N: one run is
            a single sub-second transfer, so scheduler noise on a busy
            host is material; min is the honest protocol-cost
            estimator."""
            best = float("inf")
            for _ in range(max(1, reps)):
                t0 = time.perf_counter()
                fn()
                best = min(best, time.perf_counter() - t0)
            return best

        with RpcClient(f"127.0.0.1:{ports[0]}") as legacy:
            def run_serial():
                got = chunks.fetch_bytes(
                    functools.partial(legacy.call, "cache_fetch",
                                      owner="owner", key="blob"),
                    len(data), chunk_bytes=chunk)
                assert zlib.crc32(got) == crc
            serial_s = time_best(run_serial)

        def run_pipelined():
            got = chunks.fetch_bytes_pipelined(
                pools[0], "cache_fetch", len(data), chunk_bytes=chunk,
                window=window, owner="owner", key="blob")
            assert zlib.crc32(got) == crc
        pipelined_s = time_best(run_pipelined)

        holders = {"xfer-a": pools[0], "xfer-b": pools[1]}

        def run_striped():
            buf, got_crc = transfer.fetch_striped(
                len(data), list(holders),
                lambda h, off, ln: chunks.iter_fetch_streaming(
                    holders[h], "cache_fetch_stream", ln, chunk_bytes=chunk,
                    offset=off, owner="owner", key="blob"),
                chunk_bytes=chunk)
            assert got_crc == crc
        striped_s = time_best(run_striped)

        return {
            "transfer_payload_mb": mb,
            "transfer_chunk_mb": round(chunk / (1 << 20), 2),
            "transfer_window": window,
            "transfer_serial_mib_s": mib_s(serial_s),
            "transfer_pipelined_mib_s": mib_s(pipelined_s),
            "transfer_striped_mib_s": mib_s(striped_s),
            "transfer_pipelined_speedup": round(serial_s
                                                / max(pipelined_s, 1e-9), 2),
            "transfer_striped_speedup": round(serial_s
                                              / max(striped_s, 1e-9), 2),
        }
    finally:
        for p in pools:
            p.close()
        for p in procs:
            try:
                p.stdin.close()
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001 — reap hard if need be
                p.kill()
                p.wait()


def main() -> int:
    # small-but-not-tiny payload: enough chunks for a real window, fast on CPU
    r = transfer_microbench(mb=24, chunk=1 << 20, reps=3)
    print(json.dumps(r))
    serial = r["transfer_serial_mib_s"]
    best_new = max(r["transfer_pipelined_mib_s"], r["transfer_striped_mib_s"])
    ratio = best_new / max(serial, 1e-9)
    print(f"transfer smoke: serial={serial} MiB/s, "
          f"pipelined={r['transfer_pipelined_mib_s']} MiB/s, "
          f"striped={r['transfer_striped_mib_s']} MiB/s "
          f"(best new path {ratio:.2f}x serial)")
    if best_new < serial:
        print("FAIL: pipelined/striped transfer slower than the serial "
              "baseline", file=sys.stderr)
        return 1
    print("transfer smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
