"""Test env: force an 8-device virtual CPU mesh before jax imports.

Multi-chip sharding is validated on a host-platform device mesh
(SURVEY.md §7 / driver contract); the real-TPU path is exercised by
chip_smoke.py, not the unit suite.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest

from edl_tpu.coord.memory import MemoryKV


@pytest.fixture
def memkv():
    kv = MemoryKV(sweep_period=0.1)
    yield kv
    kv.close()


@pytest.fixture
def coord_server():
    from edl_tpu.coord.server import start_server
    server = start_server("127.0.0.1", 0)
    yield server
    server.stop()


@pytest.fixture
def coord_client(coord_server):
    from edl_tpu.coord.client import CoordClient
    client = CoordClient(f"127.0.0.1:{coord_server.port}")
    yield client
    client.close()
