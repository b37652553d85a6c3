"""Delta replication plane (edl_tpu/memstate/delta): chain hashing and
torn-chain detection, freshest-recoverable cut selection, service-side
commit verification, and the end-to-end failover claim — a restore from
base + streamed chains lands PAST the committed checkpoint, survives
the owner pod's death, and every break demotes chain -> peer-full ->
storage, with the recovery record carrying ``restore_source``.

Same in-process strategy as tests/test_memstate.py: pods are
(StateCacheService, RpcServer) pairs over a MemoryKV store on the
8-device virtual CPU mesh.
"""

import functools
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu import memstate
from edl_tpu.cluster.state import State
from edl_tpu.memstate import delta
from edl_tpu.memstate import restore as ms_restore
from edl_tpu.memstate import shards as ms_shards
from edl_tpu.memstate.service import StateCacheService
from edl_tpu.memstate.tee import StateCacheTee
from edl_tpu.rpc import chunks
from edl_tpu.rpc.client import RpcClient
from edl_tpu.rpc.server import RpcServer


# -- chain format -------------------------------------------------------------
def _mk_manifest(payload: dict[str, bytes]) -> dict:
    return {k: {"crc": zlib.crc32(v), "nbytes": len(v), "dtype": "uint8",
                "shape": [len(v)], "index": [[0, len(v)]],
                "gshape": [len(v)], "leaf": k}
            for k, v in payload.items()}


def _mk_chain(base_step: int, steps: list[int], payloads=None) -> list[dict]:
    """Well-formed record dicts (manifest-listing shape) for ``steps``."""
    prev, out = delta.anchor_hash(base_step), []
    for i, step in enumerate(steps):
        man = _mk_manifest(payloads[i] if payloads else {"k": b"x" * (i + 1)})
        h = delta.chain_hash(prev, step, i + 1, man)
        out.append({"step": step, "seq": i + 1, "prev": prev, "hash": h,
                    "shards": man, "nproc": 1, "has_meta": True})
        prev = h
    return out


def test_wire_owner_roundtrip_and_reserved_prefix():
    w = delta.wire_owner("pod:a", "3", 7)
    assert delta.parse_wire_owner(w) == ("pod:a", "3", 7)
    # pod ids with colons survive (rsplit), plain owners parse to None
    assert delta.parse_wire_owner("pod-a") is None
    assert delta.parse_wire_owner("~delta:junk") is None


def test_chain_hash_covers_manifest_and_linkage():
    man = _mk_manifest({"k": b"abc"})
    h = delta.chain_hash(delta.anchor_hash(5), 10, 1, man)
    assert h == delta.chain_hash(delta.anchor_hash(5), 10, 1, dict(man))
    assert h != delta.chain_hash(delta.anchor_hash(6), 10, 1, man)
    man2 = _mk_manifest({"k": b"abd"})
    assert h != delta.chain_hash(delta.anchor_hash(5), 10, 1, man2)


def test_intact_prefix_full_and_torn():
    recs = _mk_chain(7, [10, 20, 30])
    assert [r["step"] for r in delta.intact_prefix(7, recs)] == [10, 20, 30]
    # tamper the middle record's manifest: prefix stops BEFORE it
    torn = [dict(r) for r in recs]
    torn[1] = dict(torn[1], shards=_mk_manifest({"k": b"evil"}))
    assert [r["step"] for r in delta.intact_prefix(7, torn)] == [10]
    # a seq hole is a break, not a reorder opportunity
    assert delta.intact_prefix(7, [recs[0], recs[2]]) == [recs[0]]
    # wrong anchor (base mismatch) yields nothing
    assert delta.intact_prefix(8, recs) == []


def _listing(chains: dict) -> dict:
    """cache_delta_manifest() shape from {(owner, src): (base, records)}."""
    return {f"{o}/{s}": {"owner": o, "src": s, "base_step": b, "records": r}
            for (o, s), (b, r) in chains.items()}


def test_plan_freshest_picks_common_cut():
    a = _mk_chain(7, [10, 20, 30])
    b = _mk_chain(7, [10, 20])
    plan = delta.plan_freshest(7, {"pa": _listing({("pa", "0"): (7, a)}),
                                   "pb": _listing({("pb", "0"): (7, b)})})
    # nproc=1 per record but two producers observed -> demoted
    assert plan is None
    a2 = [dict(r, nproc=2) for r in _mk_chain(7, [10, 20, 30])]
    b2 = [dict(r, nproc=2) for r in _mk_chain(7, [10, 20])]
    # rebuild hashes for the nproc field change? nproc is NOT hashed —
    # the cut rule reads it from the record as a claim
    plan = delta.plan_freshest(7, {"pa": _listing({("pa", "0"): (7, a2)}),
                                   "pb": _listing({("pb", "0"): (7, b2)})})
    assert plan is not None and plan["step"] == 20  # pb stops at 20
    assert plan["meta"]  # the step-F sidecar has holders


def test_plan_freshest_torn_chain_demotes_and_max_step_bounds():
    recs = _mk_chain(7, [10, 20, 30])
    listing = {"pa": _listing({("pa", "0"): (7, recs)})}
    assert delta.plan_freshest(7, listing)["step"] == 30
    assert delta.plan_freshest(7, listing, max_step=20)["step"] == 20
    # stale base: chains over another base are invisible
    assert delta.plan_freshest(8, listing) is None
    # torn at seq 2 -> freshest intact is 10
    torn = [recs[0], dict(recs[1], hash="0" * 40), recs[2]]
    assert delta.plan_freshest(
        7, {"pa": _listing({("pa", "0"): (7, torn)})})["step"] == 10


def test_plan_freshest_overlay_takes_latest_record_per_key():
    p1 = {"k1": b"v1-old", "k2": b"v2"}
    p2 = {"k1": b"v1-new"}
    recs = _mk_chain(7, [10, 20], payloads=[p1, p2])
    plan = delta.plan_freshest(7, {"pa": _listing({("pa", "0"): (7, recs)})})
    assert plan["step"] == 20
    # k1 resolves to the seq-2 record's copy, k2 stays at seq 1
    assert plan["overlay"]["k1"][1][0][2] == delta.wire_owner("pa", "0", 2)
    assert plan["overlay"]["k2"][1][0][2] == delta.wire_owner("pa", "0", 1)


# -- service-side commit verification ----------------------------------------
@pytest.fixture
def pod(memkv):
    srv = RpcServer("127.0.0.1", 0)
    svc = StateCacheService(memkv, "job", "pod-a")
    srv.register_instance(svc)
    srv.start()
    reg = memstate.advertise(memkv, "job", "pod-a",
                             f"127.0.0.1:{srv.port}", ttl=30)
    client = RpcClient(f"127.0.0.1:{srv.port}")
    yield svc, srv, client
    client.close()
    reg.stop()
    srv.stop()


def _stage_record(client, owner, src, base, rec, payload):
    wire = delta.wire_owner(owner, src, rec["seq"])
    for key, data in payload.items():
        chunks.push_bytes(
            functools.partial(client.call, "cache_put_chunk", owner=wire,
                              step=rec["step"], key=key), data)
    return client.call(
        "cache_delta_commit", owner=owner, src=src, base_step=base,
        step=rec["step"], seq=rec["seq"], prev_hash=rec["prev"],
        chain_hash=rec["hash"], manifest=rec["shards"], nproc=1,
        meta=b"{}")


def test_delta_commit_links_rejects_and_dedups(pod):
    svc, _srv, client = pod
    pays = [{"k": b"x"}, {"k": b"xy"}, {"k": b"xyz"}]
    recs = _mk_chain(7, [10, 20, 30], payloads=pays)
    assert _stage_record(client, "pod-a", "0", 7, recs[0], pays[0])["ok"]
    # seq hole: record 3 before record 2
    r = _stage_record(client, "pod-a", "0", 7, recs[2], pays[2])
    assert not r["ok"] and r["reason"] == "link"
    assert _stage_record(client, "pod-a", "0", 7, recs[1], pays[1])["ok"]
    # idempotent re-push of a sealed record
    r = _stage_record(client, "pod-a", "0", 7, recs[1], pays[1])
    assert r["ok"] and r.get("dup")
    # a wrong chain hash never lands
    bad = dict(recs[2], hash="0" * 40)
    r = _stage_record(client, "pod-a", "0", 7, bad, pays[2])
    assert not r["ok"] and r["reason"] == "hash"
    # a chain over an OLDER base is stale once this one exists
    old = _mk_chain(5, [6], payloads=[{"k": b"z"}])[0]
    r = _stage_record(client, "pod-a", "0", 5, old, {"k": b"z"})
    assert not r["ok"] and r["reason"] == "stale"
    listing = client.call("cache_delta_manifest")
    assert [x["seq"] for x in listing["pod-a/0"]["records"]] == [1, 2]
    # the sealed records verify end to end as an intact prefix
    assert len(delta.intact_prefix(7, listing["pod-a/0"]["records"])) == 2


def test_delta_commit_payload_crc_verified(pod):
    svc, _srv, client = pod
    rec = _mk_chain(7, [10], payloads=[{"k": b"good"}])[0]
    from edl_tpu.utils.exceptions import EdlInternalError
    with pytest.raises(EdlInternalError):
        _stage_record(client, "pod-a", "0", 7, rec, {"k": b"evil"})
    assert client.call("cache_delta_manifest") == {}


def test_delta_chain_cap_enforced(pod, monkeypatch):
    from edl_tpu.utils import constants
    monkeypatch.setattr(constants, "DELTA_MAX_CHAIN", 2)
    pays = [{"k": bytes([i])} for i in range(3)]
    recs = _mk_chain(7, [10, 20, 30], payloads=pays)
    svc, _srv, client = pod
    for i in range(2):
        assert _stage_record(client, "pod-a", "0", 7, recs[i], pays[i])["ok"]
    r = _stage_record(client, "pod-a", "0", 7, recs[2], pays[2])
    assert not r["ok"] and r["reason"] == "full"


def test_checkpoint_commit_compacts_older_base_chains(pod):
    svc, _srv, client = pod
    pay = {"k": b"v"}
    rec = _mk_chain(7, [10], payloads=[pay])[0]
    assert _stage_record(client, "pod-a", "0", 7, rec, pay)["ok"]
    assert client.call("cache_delta_manifest")
    # a full set committed at step 10 subsumes every chain over base 7
    data = b"d" * 64
    chunks.push_bytes(
        functools.partial(client.call, "cache_put_chunk", owner="pod-a",
                          step=10, key="s"), data)
    manifest = {"s": {"crc": zlib.crc32(data), "nbytes": len(data),
                      "dtype": "uint8", "shape": [64],
                      "index": [[0, 64]], "gshape": [64], "leaf": "s"}}
    assert client.call("cache_commit", owner="pod-a", step=10,
                       manifest=manifest, meta=b"{}")["ok"]
    assert client.call("cache_delta_manifest") == {}
    # and a fresh chain over the dead base is refused as stale
    rec2 = _mk_chain(7, [20], payloads=[pay])[0]
    r = _stage_record(client, "pod-a", "0", 7, rec2, pay)
    assert not r["ok"] and r["reason"] == "stale"


# -- end to end: replicator -> service -> restore -----------------------------
def _two_pods(memkv):
    pods = {}
    for pid in ("pod-a", "pod-b"):
        srv = RpcServer("127.0.0.1", 0)
        svc = StateCacheService(memkv, "job", pid)
        srv.register_instance(svc)
        srv.start()
        reg = memstate.advertise(memkv, "job", pid,
                                 f"127.0.0.1:{srv.port}", ttl=30)
        pods[pid] = (svc, srv, reg)
    return pods


def _teardown(pods):
    for _svc, srv, reg in pods.values():
        reg.stop()
        srv.stop()


def _state_and_abstract():
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("dp",))
    sh = NamedSharding(mesh, P("dp"))
    rep = NamedSharding(mesh, P())
    state = {
        "w": jax.device_put(
            np.arange(64, dtype=np.float32).reshape(8, 8), sh),
        "b": jax.device_put(np.linspace(0, 1, 6).astype(np.float32), rep),
        "step": jax.device_put(np.int32(7), rep),
    }
    abstract = {
        "w": jax.ShapeDtypeStruct((8, 8), jnp.float32, sharding=rep),
        "b": jax.ShapeDtypeStruct((6,), jnp.float32, sharding=rep),
        "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
    }
    return state, abstract


def _wait_sealed(memkv, step, timeout=30.0):
    deadline = time.monotonic() + timeout
    while memstate.read_committed_step(memkv, "job") != step:
        assert time.monotonic() < deadline, "tee never sealed the step"
        time.sleep(0.02)


def _commit_base(memkv, tmp_path, state):
    """Full set at step 7 through the real tee + checkpoint manager."""
    from edl_tpu.train.checkpoint import CheckpointManager
    tee = StateCacheTee(memkv, "job", "pod-a")
    ck = CheckpointManager(str(tmp_path / "ck"), tee=tee)
    assert ck.save(7, state, State(total_batch_size=32))
    ck.wait()
    _wait_sealed(memkv, 7)
    return ck


def _advance(state, step: int):
    """The post-training state a delta record captures."""
    out = dict(state)
    out["w"] = state["w"] + np.float32(step)
    out["step"] = jax.device_put(np.int32(step), state["step"].sharding)
    return out


def test_delta_restore_beats_committed_base(memkv, tmp_path):
    """Freshest intact chain wins: the restore lands at the delta step,
    not the checkpoint step, and survives the owner pod's death."""
    pods = _two_pods(memkv)
    try:
        state, abstract = _state_and_abstract()
        ck = _commit_base(memkv, tmp_path, state)
        rep = delta.DeltaReplicator(memkv, "job", "pod-a", every=2)
        try:
            rep.rebase(7, state)
            assert not rep.want(7) and not rep.want(9)
            assert rep.want(10)
            s10 = _advance(state, 10)
            rep.stage(10, s10, State(total_batch_size=64))
            s12 = _advance(s10, 12)
            rep.stage(12, s12, State(total_batch_size=64))
            assert rep.flush(30)
        finally:
            rep.close()
        # the probe agrees with the plan: base 7, freshest 12
        assert memstate.probe_freshest(memkv, "job") == (7, 12)
        # replica landed on pod-b for the chain AND the base set
        deadline = time.monotonic() + 30
        while ("pod-a" not in pods["pod-b"][0].cache_manifest()
               or "pod-a/0" not in pods["pod-b"][0].cache_delta_manifest()):
            assert time.monotonic() < deadline, "replication never landed"
            time.sleep(0.02)

        res = ms_restore.try_restore(memkv, "job", abstract, expect_step=7,
                                     delta_step=12)
        assert res is not None
        got, meta_json, info = res
        assert info["step"] == 12
        assert np.array_equal(np.asarray(got["w"]), np.asarray(s12["w"]))
        assert int(np.asarray(got["step"])) == 12
        # the sidecar rides the delta record, not the base
        assert State().from_json(meta_json).total_batch_size == 64
        # the unreachable target is a miss, never a different step
        assert ms_restore.try_restore(memkv, "job", abstract, expect_step=7,
                                      delta_step=14) is None
        # owner death: pod-b's replica chain alone serves the restore
        pods["pod-a"][2].stop()
        pods["pod-a"][1].stop()
        memkv.delete("/edl_tpu/job/memstate/nodes/pod-a")
        res = ms_restore.try_restore(memkv, "job", abstract, expect_step=7,
                                     delta_step=12)
        assert res is not None
        got, _meta, info = res
        assert info["step"] == 12 and info["peers"] == ["pod-b"]
        assert np.array_equal(np.asarray(got["w"]), np.asarray(s12["w"]))
        ck.close()
    finally:
        _teardown({k: v for k, v in pods.items() if k != "pod-a"})


def test_torn_chain_demotes_to_peer_full_then_storage(memkv, tmp_path):
    """The fallback matrix: CRC-broken chain -> delta restore misses;
    the plain peer-full restore still serves the base; with the cache
    gone entirely the storage path remains."""
    pods = _two_pods(memkv)
    try:
        state, abstract = _state_and_abstract()
        ck = _commit_base(memkv, tmp_path, state)
        rep = delta.DeltaReplicator(memkv, "job", "pod-a", every=2)
        try:
            rep.rebase(7, state)
            rep.stage(10, _advance(state, 10), State())
            assert rep.flush(30)
        finally:
            rep.close()
        committed, freshest = memstate.probe_freshest(memkv, "job")
        assert (committed, freshest) == (7, 10)
        # tear the chain on EVERY holder (hash no longer matches)
        for svc, _srv, _reg in pods.values():
            for ch in svc._chains.values():
                for rec in ch.records:
                    rec.manifest = {k: dict(v, crc=(int(v["crc"]) ^ 1))
                                    for k, v in rec.manifest.items()}
        assert memstate.probe_freshest(memkv, "job") == (7, None)
        assert ms_restore.try_restore(memkv, "job", abstract, expect_step=7,
                                      delta_step=10) is None
        # chain -> peer-full: the base still restores at the committed step
        res = ms_restore.try_restore(memkv, "job", abstract, expect_step=7)
        assert res is not None and res[2]["step"] == 7
        assert np.array_equal(np.asarray(res[0]["w"]), np.asarray(state["w"]))
        # peer-full -> storage: all adverts gone, Orbax still has step 7
        for pid in list(pods):
            pods[pid][2].stop()
            memkv.delete(f"/edl_tpu/job/memstate/nodes/{pid}")
        assert ms_restore.try_restore(memkv, "job", abstract,
                                      expect_step=7) is None
        stored = ck.restore(abstract)
        assert stored is not None
        assert np.array_equal(np.asarray(stored[0]["w"]),
                              np.asarray(state["w"]))
        ck.close()
    finally:
        _teardown(pods)


def test_replicator_diffs_only_changed_shards(memkv, tmp_path):
    """Record 2 carries only the keys whose CRC changed since record 1
    (the bytes/step vs full-shard win)."""
    pods = _two_pods(memkv)
    try:
        state, _abstract = _state_and_abstract()
        _commit_base(memkv, tmp_path, state).close()
        rep = delta.DeltaReplicator(memkv, "job", "pod-a", every=1)
        try:
            rep.rebase(7, state)
            s8 = _advance(state, 8)  # w + step change; b does not
            rep.stage(8, s8, State())
            s9 = dict(s8)            # ONLY step changes in record 2
            s9["step"] = jax.device_put(np.int32(9), s8["step"].sharding)
            rep.stage(9, s9, State())
            assert rep.flush(30)
        finally:
            rep.close()
        listing = pods["pod-a"][0].cache_delta_manifest()
        recs = listing["pod-a/0"]["records"]
        assert [r["seq"] for r in recs] == [1, 2]
        leaves1 = {v["leaf"] for v in recs[0]["shards"].values()}
        leaves2 = {v["leaf"] for v in recs[1]["shards"].values()}
        assert "['b']" not in leaves1 and "['w']" in leaves1
        assert leaves2 == {"['step']"}
    finally:
        _teardown(pods)


def test_replicator_cap_saturates_staging(memkv, tmp_path):
    pods = _two_pods(memkv)
    try:
        state, _abstract = _state_and_abstract()
        _commit_base(memkv, tmp_path, state).close()
        rep = delta.DeltaReplicator(memkv, "job", "pod-a", every=1,
                                    max_chain=2)
        try:
            rep.rebase(7, state)
            assert rep.want(8)
            rep.stage(8, _advance(state, 8), State())
            assert rep.want(9)
            rep.stage(9, _advance(state, 9), State())
            assert not rep.want(10)  # saturated until the next rebase
            assert rep.flush(30)
            rep.rebase(10, _advance(state, 10))
            assert rep.want(11)
        finally:
            rep.close()
    finally:
        _teardown(pods)


# -- recovery record carries restore_source=delta ----------------------------
def test_recovery_record_restore_source_delta(memkv):
    from edl_tpu.cluster.recovery import (
        summarize_recovery, write_launcher_half, write_trainer_half,
    )
    write_launcher_half(memkv, "j", "stg", "p1",
                        {"detect": 10.0, "killed": 11.0, "barrier": 12.0,
                         "spawn": 13.0})
    write_trainer_half(memkv, "j", "stg", "p1", restored=15.0,
                       first_step=16.0, restore_source="delta")
    [entry] = summarize_recovery(memkv, "j")
    assert entry["restore_source"] == "delta"
    # any pod demoted to storage downgrades the stage's source
    write_trainer_half(memkv, "j", "stg", "p2", restored=15.5,
                       first_step=16.5, restore_source="storage")
    [entry] = summarize_recovery(memkv, "j")
    assert entry["restore_source"] == "storage"
