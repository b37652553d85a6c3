"""Streaming data plane (rpc/client pool, rpc/transfer, streaming
serve_fetch): pipelined window equivalence, raw streamed frames, strict
sequence validation (gap / duplicate / dropped frame), striped
multi-holder fetch with mid-transfer demotion, and the cache-first
restore completing when a holder dies mid-stripe."""

import functools
import socket
import threading
import time
import zlib

import numpy as np
import pytest

from edl_tpu.rpc import chunks, framing, transfer
from edl_tpu.rpc.client import RpcChannelPool, RpcClient
from edl_tpu.rpc.server import RpcServer, Streaming
from edl_tpu.utils import constants
from edl_tpu.utils.exceptions import (
    EdlCoordError, EdlInternalError, EdlStreamError,
)

_RNG = np.random.default_rng(7)


# -- crc32_combine ------------------------------------------------------------
def test_crc32_combine_matches_zlib():
    data = _RNG.bytes(1 << 18)
    for cut in (0, 1, 100, 1 << 17, len(data) - 1, len(data)):
        a, b = data[:cut], data[cut:]
        assert transfer.crc32_combine(
            zlib.crc32(a), zlib.crc32(b), len(b)) == zlib.crc32(data)


def test_split_ranges_cover_and_align():
    for nbytes, n, cb in ((100, 3, 7), (1, 4, 64), (1 << 20, 2, 1 << 16),
                          (5, 8, 2)):
        ranges = transfer._split_ranges(nbytes, n, cb)
        pos = 0
        for off, ln in ranges:
            assert off == pos and ln > 0
            assert off % cb == 0
            pos += ln
        assert pos == nbytes


# -- server/pool fixtures -----------------------------------------------------
@pytest.fixture
def blob_server():
    """An RpcServer exposing chunk fetch (legacy + streaming) and a
    seq-validated push over a mutable blob store."""
    data = _RNG.bytes(3 * (1 << 20) + 123)
    staged = {}

    def fetch(offset, length):
        return data[offset:offset + length]

    def fetch_stream(offset=0, length=-1, chunk_bytes=0):
        cb = chunk_bytes or (1 << 18)
        end = len(data) if length < 0 else min(len(data), offset + length)

        def gen():
            for pos in range(offset, end, cb):
                yield memoryview(data)[pos:min(end, pos + cb)]
        return Streaming(gen())

    def push(key, seq, data, eof):
        st = staged.setdefault(key, {"buf": bytearray(), "seq": 0})
        if seq != st["seq"]:
            raise EdlInternalError(f"seq {seq} != {st['seq']}")
        st["buf"].extend(data)
        st["seq"] += 1
        st["eof"] = bool(eof)

    srv = RpcServer("127.0.0.1", 0)
    srv.register("fetch", fetch)
    srv.register("fetch_stream", fetch_stream)
    srv.register("push", push)
    srv.start()
    srv.blob = data  # type: ignore[attr-defined]
    srv.staged = staged  # type: ignore[attr-defined]
    yield srv
    srv.stop()


# -- pipelined / streaming equivalence ---------------------------------------
def test_pipelined_window1_equals_legacy_serial(blob_server):
    data = blob_server.blob
    with RpcClient(f"127.0.0.1:{blob_server.port}") as c:
        legacy = chunks.fetch_bytes(
            functools.partial(c.call, "fetch"), len(data),
            chunk_bytes=1 << 18)
    with RpcChannelPool(f"127.0.0.1:{blob_server.port}", size=1) as pool:
        w1 = chunks.fetch_bytes_pipelined(pool, "fetch", len(data),
                                          chunk_bytes=1 << 18, window=1)
        w8 = chunks.fetch_bytes_pipelined(pool, "fetch", len(data),
                                          chunk_bytes=1 << 18, window=8)
    assert legacy == data and w1 == legacy and w8 == legacy


def test_streaming_fetch_roundtrip_raw_frames(blob_server):
    data = blob_server.blob
    with RpcChannelPool(f"127.0.0.1:{blob_server.port}") as pool:
        got = b"".join(chunks.iter_fetch_streaming(
            pool, "fetch_stream", len(data), chunk_bytes=1 << 18))
        assert got == data
        # offset/length sub-range too (what a stripe asks for)
        sub = b"".join(chunks.iter_fetch_streaming(
            pool, "fetch_stream", 1 << 20, offset=12345,
            chunk_bytes=1 << 18))
        assert sub == data[12345:12345 + (1 << 20)]


def test_push_pipelined_ordered_and_windowed(blob_server):
    payload = _RNG.bytes((1 << 20) + 17)
    with RpcChannelPool(f"127.0.0.1:{blob_server.port}", size=2) as pool:
        n = chunks.push_bytes_pipelined(pool, "push", payload,
                                        chunk_bytes=1 << 16, window=6,
                                        key="k")
    assert n == -(-len(payload) // (1 << 16))
    st = blob_server.staged["k"]
    assert bytes(st["buf"]) == payload and st["eof"]


def test_pipelined_typed_error_leaves_connection_usable(blob_server):
    with RpcChannelPool(f"127.0.0.1:{blob_server.port}", size=1) as pool:
        with pytest.raises(EdlInternalError):
            # second chunk violates seq -> typed error mid-batch
            pool.call_pipelined("push", [
                {"key": "x", "seq": 0, "data": b"a", "eof": False},
                {"key": "x", "seq": 5, "data": b"b", "eof": True},
                {"key": "y", "seq": 0, "data": b"c", "eof": True},
            ], window=3)
        # frames after the error were drained; the channel still works
        assert pool.call("fetch", offset=0, length=4) == blob_server.blob[:4]
    # inc/dec paired even through the error path: nothing left in flight
    from edl_tpu.obs import metrics as obs_metrics
    assert obs_metrics.REGISTRY.get("edl_transfer_inflight_window").value == 0


# -- fault injection: crafted streams ----------------------------------------
def _crafted_stream_server(frames):
    """A raw socket server speaking just enough EDL1 to answer one
    request with pre-crafted frames (the protocol-violation injector a
    real server can't be talked into being)."""
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)

    def run():
        c, _ = srv.accept()
        try:
            framing.recv_frame(c)  # the request
            for f in frames:
                framing.send_frame(c, f)
            time.sleep(0.2)  # let the client parse before RST
        finally:
            c.close()
    threading.Thread(target=run, daemon=True).start()
    return srv


@pytest.mark.parametrize("frames,match", [
    # sequence gap: frame 1 lost somewhere
    ([{"s": None, "r": b"aa", "q": 0},
      {"s": None, "r": b"cc", "q": 2}], "gap"),
    # duplicated frame
    ([{"s": None, "r": b"aa", "q": 0},
      {"s": None, "r": b"aa", "q": 0}], "duplicate"),
    # a non-streaming answer where frames were expected
    ([{"s": None, "r": b"aa"}], "single frame"),
])
def test_stream_sequence_violations_raise_typed(frames, match):
    srv = _crafted_stream_server(frames)
    try:
        with RpcChannelPool(
                f"127.0.0.1:{srv.getsockname()[1]}", size=1) as pool:
            with pytest.raises(EdlStreamError, match=match):
                list(pool.call_streaming("m"))
    finally:
        srv.close()


def test_stream_dropped_frame_surfaces_as_short_stream():
    # server "finishes" (eof) having sent fewer bytes than the manifest
    # says: the length check, not silence, must fire
    srv = _crafted_stream_server([
        {"s": None, "r": b"x" * 10, "q": 0},
        {"s": None, "r": None, "q": 1, "eof": True},
    ])
    try:
        with RpcChannelPool(
                f"127.0.0.1:{srv.getsockname()[1]}", size=1) as pool:
            with pytest.raises(EdlStreamError, match="short"):
                list(chunks.iter_fetch_streaming(pool, "m", 64))
    finally:
        srv.close()


def test_streaming_handler_error_midway_is_typed(blob_server):
    def half_then_fail(n):
        def gen():
            yield b"z" * n
            raise EdlInternalError("holder evicted the set")
        return Streaming(gen())
    blob_server.register("flaky", half_then_fail)
    with RpcChannelPool(f"127.0.0.1:{blob_server.port}", size=1) as pool:
        got = []
        with pytest.raises(EdlInternalError, match="evicted"):
            for c in pool.call_streaming("flaky", n=7):
                got.append(c)
        assert len(got) == 1  # the good frame arrived before the error


# -- striped fetch + demotion -------------------------------------------------
def _mem_iter(data):
    def make(holder, off, ln, cb=1 << 16):
        def gen():
            for p in range(off, off + ln, cb):
                yield data[p:min(off + ln, p + cb)]
        return gen()
    return make


def test_striped_fetch_roundtrip():
    data = _RNG.bytes((1 << 21) + 999)
    buf, crc = transfer.fetch_striped(
        len(data), ["h1", "h2", "h3"],
        lambda h, off, ln: _mem_iter(data)(h, off, ln),
        chunk_bytes=1 << 16)
    assert bytes(buf) == data and crc == zlib.crc32(data)


def test_striped_holder_death_demotes_to_survivor():
    data = _RNG.bytes(1 << 21)
    served = []

    def make(holder, off, ln):
        def gen():
            if holder == "bad":
                yield data[off:off + 1024]
                raise ConnectionError("holder killed mid-stripe")
            served.append((off, ln))
            yield from _mem_iter(data)(holder, off, ln)
        return gen()

    buf, crc = transfer.fetch_striped(len(data), ["bad", "good"], make,
                                      chunk_bytes=1 << 16)
    assert bytes(buf) == data and crc == zlib.crc32(data)
    # the survivor served its own range AND the dead holder's remainder
    assert len(served) >= 2


def test_striped_every_holder_dead_raises():
    def make(holder, off, ln):
        def gen():
            raise ConnectionError(f"{holder} down")
            yield  # noqa — generator marker
        return gen()
    with pytest.raises(ConnectionError):
        transfer.fetch_striped(1 << 20, ["a", "b"], make,
                               chunk_bytes=1 << 16)


# -- fetch_bytes diagnostics (the unsafe-len fix) -----------------------------
def test_fetch_bytes_bad_result_diagnostic_is_safe():
    with pytest.raises(ConnectionError, match=r"cache_fetch w@pod.*dict"):
        chunks.fetch_bytes(lambda offset, length: {"oops": 1}, 10,
                           chunk_bytes=4, label="cache_fetch w@pod")
    with pytest.raises(ConnectionError, match="NoneType"):
        chunks.fetch_bytes(lambda offset, length: None, 10, chunk_bytes=4)
    with pytest.raises(ConnectionError, match="3 bytes"):
        chunks.fetch_bytes(lambda offset, length: b"abc", 10, chunk_bytes=4)


# -- restore completes when a holder dies mid-stripe --------------------------
def test_restore_survives_holder_killed_mid_stripe(memkv, monkeypatch):
    import jax

    from edl_tpu import memstate
    from edl_tpu.memstate import restore as ms_restore
    from edl_tpu.memstate.service import StateCacheService

    # small knobs so a 4 MB shard stripes across both holders
    monkeypatch.setattr(constants, "STRIPE_MIN_BYTES", 1 << 20)
    monkeypatch.setattr(constants, "MEMSTATE_CHUNK_BYTES", 1 << 18)

    arr = np.arange(1 << 20, dtype=np.float32)  # 4 MB
    data = arr.tobytes()
    key = "['w']@0:%d" % len(arr)
    ent = {"crc": zlib.crc32(data), "nbytes": len(data), "dtype": "float32",
           "shape": [len(arr)], "index": [[0, len(arr)]],
           "gshape": [len(arr)], "leaf": "['w']"}

    servers, regs = [], []
    try:
        for pid in ("pod-a", "pod-b"):
            svc = StateCacheService(memkv, "job", pid)
            svc.cache_put_chunk("pod-a", 3, key, 0, data, True)
            svc.cache_commit("pod-a", 3, manifest={key: ent}, meta=b"{}")
            srv = RpcServer("127.0.0.1", 0)
            srv.register_instance(svc)
            if pid == "pod-a":
                # pod-a dies one chunk into ANY streamed range
                orig = svc.cache_fetch_stream

                def flaky(owner, key, offset=0, length=-1, chunk_bytes=0,
                          _orig=orig):
                    inner = _orig(owner, key, offset=offset, length=length,
                                  chunk_bytes=chunk_bytes).it

                    def gen():
                        yield next(inner)
                        raise ConnectionError("holder killed mid-stripe")
                    return Streaming(gen())
                srv.register("cache_fetch_stream", flaky)
            srv.start()
            servers.append(srv)
            regs.append(memstate.advertise(memkv, "job", pid,
                                           f"127.0.0.1:{srv.port}", ttl=30))
        memstate.write_committed_step(memkv, "job", 3)

        rep = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
        abstract = {"w": jax.ShapeDtypeStruct((len(arr),), np.float32,
                                              sharding=rep)}
        res = ms_restore.try_restore(memkv, "job", abstract, expect_step=3)
        assert res is not None, "restore must complete from the survivor"
        got, meta_json, info = res
        assert np.array_equal(np.asarray(got["w"]), arr)
        assert meta_json == "{}"
        assert "pod-b" in info["peers"]
    finally:
        for r in regs:
            r.stop()
        for s in servers:
            s.stop()


def test_restore_from_old_peer_without_streaming(memkv):
    """Fallback matrix: a peer that predates ``cache_fetch_stream``
    (only the one-chunk-per-call surface) still serves a restore via
    the pipelined legacy path."""
    import jax

    from edl_tpu import memstate
    from edl_tpu.memstate import restore as ms_restore
    from edl_tpu.memstate.service import StateCacheService

    arr = np.linspace(0, 1, 4096).astype(np.float32)
    data = arr.tobytes()
    key = "['w']@0:%d" % len(arr)
    ent = {"crc": zlib.crc32(data), "nbytes": len(data), "dtype": "float32",
           "shape": [len(arr)], "index": [[0, len(arr)]],
           "gshape": [len(arr)], "leaf": "['w']"}
    svc = StateCacheService(memkv, "job", "old-pod")
    svc.cache_put_chunk("old-pod", 9, key, 0, data, True)
    svc.cache_commit("old-pod", 9, manifest={key: ent}, meta=b"{}")
    srv = RpcServer("127.0.0.1", 0)
    # an OLD peer: expose everything EXCEPT the streaming method
    for name in ("cache_manifest", "cache_fetch", "cache_meta"):
        srv.register(name, getattr(svc, name))
    srv.start()
    reg = memstate.advertise(memkv, "job", "old-pod",
                             f"127.0.0.1:{srv.port}", ttl=30)
    try:
        memstate.write_committed_step(memkv, "job", 9)
        rep = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
        abstract = {"w": jax.ShapeDtypeStruct((len(arr),), np.float32,
                                              sharding=rep)}
        res = ms_restore.try_restore(memkv, "job", abstract, expect_step=9)
        assert res is not None
        assert np.array_equal(np.asarray(res[0]["w"]), arr)
    finally:
        reg.stop()
        srv.stop()


# -- the connect-outside-the-lock regression ----------------------------------
def test_dead_endpoint_does_not_serialize_concurrent_callers(monkeypatch):
    """PR-2 bug: RpcClient.call held the client lock across _connect,
    so one dead endpoint cost N callers N × the connect timeout, in
    series.  Connects now happen outside the lock: N callers fail in
    ~one timeout, in parallel."""
    from edl_tpu.rpc import client as client_mod

    delay = 0.4

    def slow_connect(endpoint, timeout):
        time.sleep(delay)
        raise OSError("connect timed out")

    monkeypatch.setattr(client_mod, "_connect", slow_connect)
    c = RpcClient("198.51.100.1:9", timeout=1.0)
    outcomes = []

    def worker():
        try:
            c.call("ping")
        except EdlCoordError:
            outcomes.append("coord")
        except Exception as e:  # noqa: BLE001
            outcomes.append(repr(e))

    threads = [threading.Thread(target=worker) for _ in range(4)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    assert outcomes == ["coord"] * 4
    # each caller: 2 attempts x 0.4 s, all callers in PARALLEL.  The
    # serialized behavior would take >= 4 x 0.8 = 3.2 s; allow slack
    assert wall < 2.4, f"dead-endpoint connects serialized: {wall:.2f}s"
