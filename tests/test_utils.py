"""Utils layer: serialization round-trips, typed-error wire contract, retry."""

import time

import pytest

from edl_tpu.utils import exceptions
from edl_tpu.utils.retry import retry_until_timeout
from edl_tpu.utils.serialization import JsonSerializable, register_serializable


@register_serializable
class _Inner(JsonSerializable):
    def __init__(self, x=0, tags=None):
        self.x = x
        self.tags = tags or []


@register_serializable
class _Outer(JsonSerializable):
    def __init__(self):
        self.name = "outer"
        self.items = [_Inner(1, ["a"]), _Inner(2)]
        self.child = _Inner(3)
        self.meta = {"k": 1}


def test_nested_roundtrip():
    o = _Outer()
    o2 = _Outer().from_json(o.to_json())
    assert o == o2
    assert isinstance(o2.items[0], _Inner)
    assert o2.items[0].x == 1 and o2.child.x == 3
    o2.child.x = 99
    assert o != o2


def test_exception_wire_roundtrip():
    status = exceptions.serialize(exceptions.EdlBarrierError("not yet"))
    with pytest.raises(exceptions.EdlBarrierError, match="not yet"):
        exceptions.deserialize(status)
    # unknown/untyped exceptions arrive as EdlInternalError with traceback
    status = exceptions.serialize(ValueError("boom"))
    with pytest.raises(exceptions.EdlInternalError, match="boom"):
        exceptions.deserialize(status)
    assert exceptions.deserialize(None) is None


def test_retry_until_timeout_succeeds_then_gives_up():
    calls = {"n": 0}

    @retry_until_timeout(interval=0.01)
    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise exceptions.EdlBarrierError("wait")
        return "ok"

    assert flaky(timeout=5.0) == "ok"
    assert calls["n"] == 3

    @retry_until_timeout(interval=0.01)
    def always_fails():
        raise exceptions.EdlBarrierError("never")

    t0 = time.monotonic()
    with pytest.raises(exceptions.EdlBarrierError):
        always_fails(timeout=0.1)
    assert time.monotonic() - t0 < 2.0

    @retry_until_timeout(interval=0.01)
    def hard_error():
        calls["n"] += 1
        raise ValueError("no retry")

    calls["n"] = 0
    with pytest.raises(ValueError):
        hard_error(timeout=1.0)
    assert calls["n"] == 1


def test_logger_configure_file_handler_idempotent(tmp_path):
    """Repeated configure(log_dir=...) must not stack duplicate file
    handlers (every line would log N times); a DIFFERENT file is a new
    handler."""
    import logging

    from edl_tpu.utils.logger import configure

    root = logging.getLogger("edl_tpu")
    before = list(root.handlers)
    try:
        configure(log_dir=str(tmp_path), filename="a.log")
        configure(log_dir=str(tmp_path), filename="a.log")
        configure(log_dir=str(tmp_path), filename="a.log")
        added = [h for h in root.handlers if h not in before]
        files = [h for h in added if isinstance(h, logging.FileHandler)]
        assert len(files) == 1
        configure(log_dir=str(tmp_path), filename="b.log")
        added = [h for h in root.handlers if h not in before]
        files = [h for h in added if isinstance(h, logging.FileHandler)]
        assert len(files) == 2
    finally:
        for h in list(root.handlers):
            if h not in before:
                root.removeHandler(h)
                h.close()


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the helper touches nothing (jax
    reads the variable itself).  Unset: one in-checkout path, the same
    on every call and for every process — never a temp dir, a pid or
    the clock, which would make a cache that cannot hit."""
    import os

    import jax

    from edl_tpu.utils.compile_cache import enable_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert enable_compile_cache() == "/x"
    assert updates == []

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert enable_compile_cache() == enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)] * 2
