"""Coordination server: MemoryKV exposed over the framed-msgpack RPC.

Run standalone (``python -m edl_tpu.coord.server --port 2379``) the way
the reference's tests booted a local etcd binary (etcd_test.sh), or
embed via :func:`start_server`.  The native C++ daemon
(csrc/coordd.cc, built on demand by
``edl_tpu.native.build.ensure_coordd``) serves the identical method
set and wire format; the coordination test battery runs against both
backends (tests/test_coord.py), so either is a drop-in for production.
"""

from __future__ import annotations

import argparse
import functools
import os
import time

from edl_tpu.cluster import paths
from edl_tpu.coord.memory import MemoryKV
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.rpc.server import RpcServer
from edl_tpu.utils import constants
from edl_tpu.utils.logger import configure, get_logger

logger = get_logger(__name__)

_KV_OPS_TOTAL = obs_metrics.counter(
    "edl_kv_ops_total", "Coordination KV ops served, by op", ("op",))
_KV_OP_SECONDS = obs_metrics.histogram(
    "edl_kv_op_seconds", "Coordination KV op service time (seconds); "
    "`wait` blocks until an event or its timeout", ("op",))
_COORD_OP_SECONDS = obs_metrics.histogram(
    "edl_coord_op_seconds",
    "Coordination op service time by op and key table — the per-table "
    "split attributes control-plane latency to its writer (doc/scale.md)",
    ("op", "table"))
_TABLE_WRITES_TOTAL = obs_metrics.counter(
    "edl_coord_table_writes_total",
    "Mutating coordination ops by key table (hot-prefix write counter)",
    ("table",))

# mutating wire methods (feed the hot-prefix write counter)
_WRITE_OPS = frozenset({"kv_put", "kv_del", "kv_del_range",
                        "txn_put_if_absent", "txn_put_if_equals"})
_TABLES = frozenset(constants.ALL_TABLES)


def _table_of(kw: dict) -> str:
    """Key table of a wire call's kwargs, from the canonical
    ``/edl_tpu/<job_id>/<table>/<name>`` schema (cluster/paths.py).
    Cardinality is bounded by construction: only the known table set
    mints label values — any other key shape is "other", key-less ops
    (leases, ping) are ""."""
    key = kw.get("key") or kw.get("prefix") or kw.get("guard_key") or ""
    if not key:
        return ""
    if key.startswith(paths.ROOT + "/"):
        parts = key.split("/", 4)
        if len(parts) >= 4 and parts[3] in _TABLES:
            return parts[3]
    return "other"


def _timed(fn):
    """Count + time each KV op (op = wire method name, table parsed
    from the key/prefix kwarg — RPC dispatch always calls by kwargs)."""
    op = fn.__name__
    is_write = op in _WRITE_OPS

    @functools.wraps(fn)
    def wrapper(self, *a, **kw):
        table = _table_of(kw)
        if is_write:
            _TABLE_WRITES_TOTAL.labels(table=table).inc()
        t0 = time.perf_counter()
        try:
            return fn(self, *a, **kw)
        finally:
            dt = time.perf_counter() - t0
            _KV_OPS_TOTAL.labels(op=op).inc()
            _KV_OP_SECONDS.labels(op=op).observe(dt)
            _COORD_OP_SECONDS.labels(op=op, table=table).observe(dt)

    return wrapper


def _rec_to_wire(rec):
    return None if rec is None else [rec.key, rec.value, rec.revision, rec.lease_id]


class CoordService:
    """RPC facade over a KVStore; method names are the wire protocol."""

    def __init__(self, kv: MemoryKV):
        self._kv = kv

    @_timed
    def kv_put(self, key, value, lease_id=0):
        return {"rev": self._kv.put(key, value, lease_id)}

    @_timed
    def kv_get(self, key):
        return {"rec": _rec_to_wire(self._kv.get(key))}

    @_timed
    def kv_range(self, prefix):
        recs, rev = self._kv.get_prefix(prefix)
        return {"recs": [_rec_to_wire(r) for r in recs], "rev": rev}

    @_timed
    def kv_del(self, key):
        return {"deleted": self._kv.delete(key)}

    @_timed
    def kv_del_range(self, prefix):
        return {"n": self._kv.delete_prefix(prefix)}

    @_timed
    def lease_grant(self, ttl):
        return {"lease_id": self._kv.lease_grant(ttl)}

    @_timed
    def lease_keepalive(self, lease_id):
        return {"alive": self._kv.lease_keepalive(lease_id)}

    @_timed
    def lease_revoke(self, lease_id):
        self._kv.lease_revoke(lease_id)
        return {}

    @_timed
    def txn_put_if_absent(self, key, value, lease_id=0):
        return {"succeeded": self._kv.put_if_absent(key, value, lease_id)}

    @_timed
    def txn_put_if_equals(self, guard_key, guard_value, key, value, lease_id=0):
        return {"succeeded": self._kv.put_if_equals(guard_key, guard_value, key, value, lease_id)}

    @_timed
    def wait(self, prefix, since_revision, timeout):
        res = self._kv.wait(prefix, since_revision, min(float(timeout), 60.0))
        return {"events": [[e.type, _rec_to_wire(e.record)] for e in res.events],
                "rev": res.revision, "snap": res.snapshot}

    @_timed
    def ping(self):
        return {"pong": True}

    @_timed
    def dump_state(self):
        """Debug/chaos surface: the canonical time-independent state
        image (revision counter, lease table, every record) — the chaos
        smoke asserts a WAL-backed restart reproduces it bit-exactly."""
        return {"state": self._kv.dump_state()}


def start_server(host: str = "0.0.0.0", port: int = 0,
                 kv: MemoryKV | None = None,
                 data_dir: str | None = None,
                 restart_grace: float | None = None) -> RpcServer:
    """Boot the RPC server; ``data_dir`` (or ``EDL_TPU_COORD_DATA_DIR``)
    makes the store durable: WAL + snapshot, replayed on restart."""
    if kv is None:
        data_dir = constants.COORD_DATA_DIR if data_dir is None else data_dir
        if data_dir:
            from edl_tpu.coord.wal import open_durable
            kv = open_durable(data_dir, restart_grace=restart_grace)
        else:
            kv = MemoryKV()
    server = RpcServer(host, port)
    server.register_instance(CoordService(kv))
    server.kv = kv  # owner handle: in-process restarts close the WAL
    return server.start()


def spawn_subprocess(port: int, data_dir: str,
                     restart_grace: float | None = None,
                     host: str = "127.0.0.1", env: dict | None = None):
    """Spawn ``python -m edl_tpu.coord.server`` as a subprocess — the
    SIGKILL-able real thing the chaos smoke drills."""
    import subprocess
    import sys

    argv = [sys.executable, "-m", "edl_tpu.coord.server", "--host", host,
            "--port", str(port), "--data_dir", data_dir]
    if restart_grace is not None:
        argv += ["--restart_grace", str(restart_grace)]
    return subprocess.Popen(argv, env=env or dict(os.environ),
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.STDOUT)


def wait_ready(endpoint: str, deadline_s: float = 120.0) -> float:
    """Block until ``endpoint`` answers a coordination ping; returns the
    seconds waited (the restart-MTTR building block)."""
    from edl_tpu.coord.client import CoordClient

    t0 = time.monotonic()
    deadline = t0 + deadline_s
    while time.monotonic() < deadline:
        probe = CoordClient(endpoint, timeout=1.0)
        try:
            if probe.ping():
                return time.monotonic() - t0
        # edl-lint: disable=wire-error — boot-poll: failure IS the
        # expected state until the server answers; the loop's timeout
        # raises with the endpoint when it never does
        except Exception:  # noqa: BLE001 — still booting
            pass
        finally:
            probe.close()
        time.sleep(0.05)
    raise TimeoutError(f"coord server at {endpoint} never answered")


def main():
    parser = argparse.ArgumentParser("edl_tpu coordination server")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=2379)
    parser.add_argument("--data_dir", default=constants.COORD_DATA_DIR,
                        help="WAL + snapshot directory; empty = in-memory "
                             "only (a restart loses all state)")
    parser.add_argument("--restart_grace", type=float, default=None,
                        help="seconds to suspend expiry sweeps after a "
                             "WAL-backed restart (-1/unset = one TTL)")
    parser.add_argument("--job_id", default=os.environ.get("EDL_TPU_JOB_ID", ""),
                        help="advertise this server's env-gated /metrics "
                             "endpoint in its OWN store under the job's obs "
                             "table, so edl-obs-agg scrapes the coord "
                             "telemetry and edl-obs-top shows the "
                             "control-plane pane (empty = no advert)")
    args = parser.parse_args()
    configure()
    from edl_tpu import obs
    obs.install_from_env("coord")  # /metrics + JSONL trace, env-gated
    server = start_server(args.host, args.port, data_dir=args.data_dir,
                          restart_grace=args.restart_grace)
    if args.job_id:
        # in-process store handle: the advert rides a TTL lease in the
        # server's own KV, kept alive for the life of this process —
        # best-effort (advertise_installed never raises), and a no-op
        # unless EDL_TPU_METRICS_PORT enabled the endpoint above
        from edl_tpu.obs import advert
        advert.advertise_installed(server.kv, args.job_id, "coord")
    logger.info("coordination server listening on %s%s", server.endpoint,
                f" (durable: {args.data_dir})" if args.data_dir else "")
    try:
        import threading
        threading.Event().wait()
    except KeyboardInterrupt:
        server.stop()


if __name__ == "__main__":
    main()
