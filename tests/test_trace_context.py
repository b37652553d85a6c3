"""Distributed trace context: ambient propagation, the EDL1 wire
(client inject → server re-establish, including nested hops and the
chunked-RPC path), thread isolation, and the env handoff the launcher
uses to pull spawned trainers into a resize epoch's trace."""

import functools
import json
import threading
import time

import pytest

from edl_tpu.obs import context as obs_context
from edl_tpu.obs import trace as obs_trace
from edl_tpu.rpc import chunks
from edl_tpu.rpc.client import RpcClient
from edl_tpu.rpc.server import RpcServer


@pytest.fixture
def make_server():
    servers = []

    def make() -> RpcServer:
        srv = RpcServer("127.0.0.1", 0)
        servers.append(srv)
        return srv

    yield make
    for srv in servers:
        srv.stop()


@pytest.fixture(autouse=True)
def clean_process_root():
    yield
    obs_context.set_process_root(None)


def _read_events(path):
    return [json.loads(line) for line in path.read_text().splitlines()]


# -- context basics ----------------------------------------------------------

def test_child_keeps_trace_links_parent():
    root = obs_context.new_trace(stage="s1")
    child = root.child()
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    assert child.span_id != root.span_id
    assert child.baggage == {"stage": "s1"}


def test_wire_and_env_roundtrip():
    ctx = obs_context.new_trace(job="j")
    back = obs_context.TraceContext.from_wire(ctx.to_wire())
    assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)
    assert back.baggage == {"job": "j"}
    env = obs_context.TraceContext.from_env_value(ctx.to_env())
    assert env.trace_id == ctx.trace_id
    # garbage never raises — a bad peer can't crash a handler
    assert obs_context.TraceContext.from_wire(None) is None
    assert obs_context.TraceContext.from_wire({"t": 1, "s": "x"}) is None
    assert obs_context.TraceContext.from_env_value("not json") is None


def test_use_restores_previous_context():
    a, b = obs_context.new_trace(), obs_context.new_trace()
    assert obs_context.current() is None
    with obs_context.use(a):
        assert obs_context.current().trace_id == a.trace_id
        with obs_context.use(b):
            assert obs_context.current().trace_id == b.trace_id
        assert obs_context.current().trace_id == a.trace_id
    assert obs_context.current() is None
    with obs_context.use(None):   # None is a no-op branch-free call site
        assert obs_context.current() is None


def test_process_root_is_fallback_for_new_threads():
    root = obs_context.new_trace()
    obs_context.set_process_root(root)
    seen = {}

    def worker():
        seen["ctx"] = obs_context.current()

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert seen["ctx"].trace_id == root.trace_id
    # an explicitly attached context beats the root
    other = obs_context.new_trace()
    with obs_context.use(other):
        assert obs_context.current().trace_id == other.trace_id


def test_install_from_env(monkeypatch):
    ctx = obs_context.new_trace(stage="e1")
    monkeypatch.setenv(obs_context.ENV_VAR, ctx.to_env())
    got = obs_context.install_from_env()
    assert got.trace_id == ctx.trace_id
    assert obs_context.current().trace_id == ctx.trace_id


# -- tracer integration ------------------------------------------------------

def test_tracer_attaches_ids_only_with_context(tmp_path):
    tr = obs_trace.Tracer(str(tmp_path / "t.jsonl"), "unit")
    tr.emit("plain", at=1.0)
    ctx = obs_context.new_trace()
    with obs_context.use(ctx):
        tr.emit("traced", at=2.0)
    tr.close()
    plain, traced = _read_events(tmp_path / "t.jsonl")
    assert "trace_id" not in plain and "span_id" not in plain
    assert traced["trace_id"] == ctx.trace_id
    assert traced["span_id"] == ctx.span_id


def test_nested_spans_link_parents(tmp_path):
    tr = obs_trace.Tracer(str(tmp_path / "t.jsonl"), "unit")
    ctx = obs_context.new_trace()
    with obs_context.use(ctx):
        with tr.span("outer"):
            with tr.span("inner"):
                pass
    tr.close()
    inner, outer = _read_events(tmp_path / "t.jsonl")  # inner exits first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["trace_id"] == outer["trace_id"] == ctx.trace_id
    assert inner["parent_id"] == outer["span_id"]
    assert outer["parent_id"] == ctx.span_id
    # span ts is the BEGIN: outer started no later than inner
    assert outer["ts"] <= inner["ts"]


# -- the wire ----------------------------------------------------------------

def test_rpc_handler_inherits_caller_trace(make_server, tmp_path):
    tr = obs_trace.configure(str(tmp_path / "srv.jsonl"), "server")
    try:
        def handler():
            obs_trace.emit("srv/handled")
            cur = obs_context.current()
            return {"trace": cur.trace_id if cur else None,
                    "parent": cur.parent_id if cur else None}

        srv = make_server()
        srv.register("do", handler)
        srv.start()
        ctx = obs_context.new_trace()
        with RpcClient(f"127.0.0.1:{srv.port}") as client:
            with obs_context.use(ctx):
                r = client.call("do")
            # outside any context the handler must see none
            r_none = client.call("do")
        assert r["trace"] == ctx.trace_id
        assert r["parent"] == ctx.span_id      # handler runs as a child span
        assert r_none["trace"] is None, "context leaked across requests"
    finally:
        obs_trace.install(obs_trace.NullTracer())
        tr.close()
    with_ctx, without_ctx = [e for e in _read_events(tmp_path / "srv.jsonl")
                             if e["name"] == "srv/handled"]
    assert with_ctx["trace_id"] == ctx.trace_id
    assert "trace_id" not in without_ctx


def test_nested_client_server_client_hop_keeps_trace(make_server):
    inner = make_server()
    inner.register("leaf", lambda: {
        "trace": obs_context.current().trace_id
        if obs_context.current() else None})
    inner.start()

    def middle():
        with RpcClient(f"127.0.0.1:{inner.port}") as c:
            return c.call("leaf")

    outer = make_server()
    outer.register("mid", middle)
    outer.start()
    ctx = obs_context.new_trace()
    with obs_context.use(ctx), RpcClient(f"127.0.0.1:{outer.port}") as c:
        r = c.call("mid")
    assert r["trace"] == ctx.trace_id, "trace lost across the second hop"


def test_chunked_rpc_path_carries_context(make_server):
    got: list[tuple[int, str | None]] = []
    buf = bytearray()

    def push(seq: int, data: bytes, eof: bool):
        cur = obs_context.current()
        got.append((seq, cur.trace_id if cur else None))
        buf.extend(data)
        return {"ok": True}

    def fetch(offset: int, length: int) -> bytes:
        cur = obs_context.current()
        got.append((-1, cur.trace_id if cur else None))
        return bytes(buf[offset:offset + length])

    srv = make_server()
    srv.register("push", push)
    srv.register("fetch", fetch)
    srv.start()
    payload = bytes(range(256)) * 40
    ctx = obs_context.new_trace()
    with obs_context.use(ctx), RpcClient(f"127.0.0.1:{srv.port}") as c:
        n = chunks.push_bytes(functools.partial(c.call, "push"), payload,
                              chunk_bytes=1024)
        back = chunks.fetch_bytes(functools.partial(c.call, "fetch"),
                                  len(payload), chunk_bytes=1024)
    assert n > 1 and back == payload
    assert got and all(t == ctx.trace_id for _, t in got), \
        "every chunk RPC must carry the ambient trace"


def test_concurrent_handlers_never_cross_contexts(make_server):
    def slow_echo(tag: str):
        time.sleep(0.02)
        cur = obs_context.current()
        return {"tag": tag, "trace": cur.trace_id if cur else None}

    srv = make_server()
    srv.register("echo", slow_echo)
    srv.start()
    errors: list[str] = []

    def client_loop(i: int):
        ctx = obs_context.new_trace()
        try:
            with RpcClient(f"127.0.0.1:{srv.port}") as c:
                for _ in range(10):
                    with obs_context.use(ctx):
                        r = c.call("echo", tag=str(i))
                    if r["trace"] != ctx.trace_id:
                        errors.append(
                            f"client {i} saw {r['trace']}")
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=client_loop, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors


# -- the engine's request event joins the gateway's trace --------------------

def test_engine_request_carries_gateway_trace_through_replica(memkv, tmp_path):
    """gateway/request > gateway/route > serving/submit > engine/request
    > serving/complete: the engine thread has no ambient context, so
    ``submit()`` captures the RPC handler's and ``_finish`` pins it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from edl_tpu.models import TransformerConfig, TransformerLM
    from edl_tpu.serving import ContinuousBatcher
    from edl_tpu.serving.replica import ReplicaServer

    cfg = TransformerConfig(vocab_size=53, num_layers=1, embed_dim=32,
                            num_heads=2, mlp_dim=64, max_len=64,
                            remat=False, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    eng = ContinuousBatcher(cfg, params, slots=2, temperature=0.0,
                            prefill_buckets=(8,), steps_per_sync=2)
    srv = ReplicaServer(memkv, "job", eng, replica_id="r0",
                        host="127.0.0.1", ttl=5, advert_period=0.2)
    tr = obs_trace.configure(str(tmp_path / "replica.jsonl"), "replica")
    gateway = obs_context.new_trace()       # what gate_generate stamps
    try:
        with RpcClient(srv.endpoint, 120) as client:
            with obs_context.use(gateway):
                client.call("serve_submit", request_id="q1",
                            prompt=[3, 1, 4, 1, 5], max_new=4)
                deadline = time.monotonic() + 120
                while not client.call("serve_wait", request_id="q1",
                                      timeout=5.0)["done"]:
                    assert time.monotonic() < deadline
            # a request from nobody's trace stays outside it
            client.call("serve_submit", request_id="q2", prompt=[2, 7],
                        max_new=2)
            while not client.call("serve_wait", request_id="q2",
                                  timeout=5.0)["done"]:
                assert time.monotonic() < deadline
    finally:
        obs_trace.install(obs_trace.NullTracer())
        tr.close()
        srv.close()
        eng.stop()
    events = _read_events(tmp_path / "replica.jsonl")
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    submit = by_name["serving/submit"][0]
    traced, plain = by_name["engine/request"]
    assert submit["trace_id"] == gateway.trace_id
    assert traced["trace_id"] == gateway.trace_id
    assert traced["parent_id"] == submit["span_id"]   # child of the submit
    assert traced["span_id"] not in (submit["span_id"], gateway.span_id)
    assert traced["n_prompt"] == 5 and traced["n_out"] == 4
    assert "trace_id" not in plain and plain["n_prompt"] == 2
    # in the file the engine's event lies between submit and complete
    names = [e["name"] for e in events if e.get("request") == "q1"
             or e is traced]
    assert names == ["serving/submit", "engine/request", "serving/complete"]
    from edl_tpu.obs import dump as obs_dump
    tl = obs_dump.merge_timeline(events, gateway.trace_id)
    assert [e["name"] for e in tl if e["name"].startswith(
        ("serving/", "engine/"))].count("engine/request") == 1
