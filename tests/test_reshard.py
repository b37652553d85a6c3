"""Delta-resize placement diff (memstate/reshard.py) + the resize
handshake records (cluster/resize.py).

The plan is a pure function: these tests pin the properties the live
path leans on — only changed-owner shards move, survivor seats are
stable, input enumeration order is irrelevant, and the move source is
the departed owner's ring replica when it survives.
"""

from edl_tpu.cluster import resize as resize_rec
from edl_tpu.memstate import placement
from edl_tpu.memstate.reshard import reshard_plan, stable_ranking


def shards_for(owners: dict[str, int], nbytes: int = 100) -> dict:
    """{owner: n_shards} -> manifest-shaped shards dict."""
    out = {}
    for owner, n in owners.items():
        for i in range(n):
            out[f"['w']@{owner[-1]}{i}:0"] = {"owner": owner,
                                              "nbytes": nbytes}
    return out


# -- stable_ranking --------------------------------------------------------
def test_stable_ranking_survivors_keep_order_joiners_sorted():
    assert stable_ranking(["b", "a", "c"], ["c", "a", "z", "x"]) == \
        ["a", "c", "x", "z"]


def test_stable_ranking_ignores_new_pod_enumeration_order():
    old = ["p1", "p2", "p3"]
    assert stable_ranking(old, ["p9", "p3", "p1"]) == \
        stable_ranking(old, ["p1", "p3", "p9"]) == ["p1", "p3", "p9"]


# -- reshard_plan ----------------------------------------------------------
def test_grow_by_one_moves_nothing():
    old = ["pod-a", "pod-b"]
    shards = shards_for({"pod-a": 3, "pod-b": 2})
    plan = reshard_plan(old, ["pod-a", "pod-b", "pod-c"], shards)
    assert plan.moves == []
    assert plan.kept_bytes == 500 and plan.moved_bytes == 0
    assert plan.kept_fraction == 1.0
    assert plan.shards_total == 5
    assert plan.ranking == ["pod-a", "pod-b", "pod-c"]


def test_shrink_by_one_moves_only_the_departed_owners_shards():
    old = ["pod-a", "pod-b", "pod-c"]
    shards = shards_for({"pod-a": 2, "pod-b": 2, "pod-c": 3})
    plan = reshard_plan(old, ["pod-a", "pod-b"], shards)
    assert sorted(m.key for m in plan.moves) == \
        sorted(k for k, e in shards.items() if e["owner"] == "pod-c")
    assert all(m.old_owner == "pod-c" for m in plan.moves)
    assert plan.moved_bytes == 300 and plan.kept_bytes == 400
    # the departed rank-2 seat folds onto rank 2 % 2 = 0
    assert all(m.new_owner == "pod-a" for m in plan.moves)


def test_shrink_source_is_the_surviving_ring_replica():
    old = ["pod-a", "pod-b", "pod-c"]
    shards = shards_for({"pod-c": 2})
    plan = reshard_plan(old, ["pod-a", "pod-b"], shards)
    want = placement.replica_for("pod-c", old)
    assert want in {"pod-a", "pod-b"}  # ring replica survived
    assert all(m.src == want for m in plan.moves)


def test_swap_moves_only_the_departed_owner_to_the_joiner_seat():
    old = ["pod-a", "pod-b", "pod-c"]
    new = ["pod-a", "pod-c", "pod-d"]  # b left, d joined
    shards = shards_for({"pod-a": 2, "pod-b": 2, "pod-c": 2})
    plan = reshard_plan(old, new, shards)
    assert all(m.old_owner == "pod-b" for m in plan.moves)
    assert len(plan.moves) == 2
    # survivors keep their shards even though pod-c's RANK changed
    assert sorted(plan.kept) == sorted(
        k for k, e in shards.items() if e["owner"] != "pod-b")
    # pod-b sat at rank 1; the canonical new ranking [a, c, d] seats
    # pod-c there — the seat moves with the rank, not the identity
    assert all(m.new_owner == "pod-c" for m in plan.moves)


def test_plan_stable_under_pod_set_reordering():
    old = ["pod-a", "pod-b", "pod-c"]
    shards = shards_for({"pod-a": 1, "pod-b": 2, "pod-c": 3})
    p1 = reshard_plan(old, ["pod-d", "pod-a", "pod-b"], shards)
    p2 = reshard_plan(old, ["pod-b", "pod-d", "pod-a"], shards)
    assert p1.ranking == p2.ranking == ["pod-a", "pod-b", "pod-d"]
    assert [(m.key, m.src, m.new_owner) for m in p1.moves] == \
        [(m.key, m.src, m.new_owner) for m in p2.moves]
    assert p1.kept == p2.kept


def test_plan_with_no_surviving_copy_marks_src_none():
    # both the owner AND its ring replica departed: the move has no
    # cache source (restore falls back to storage for those shards)
    old = ["pod-a", "pod-b"]
    shards = shards_for({"pod-b": 1})
    replica = placement.replica_for("pod-b", old)
    assert replica == "pod-a"
    plan = reshard_plan(old, ["pod-x"], shards)
    assert [m.src for m in plan.moves] == [None]


def test_empty_shards_is_a_full_keep():
    plan = reshard_plan(["a"], ["a", "b"], {})
    assert plan.kept_fraction == 1.0 and plan.moves == []


# -- handshake records -----------------------------------------------------
def test_resize_records_roundtrip(memkv):
    resize_rec.flag_resize(memkv, "j", "s-old", "grow", "s-new", "pod-a")
    flag = resize_rec.read_resize_flag(memkv, "j", "s-old")
    assert flag["mode"] == "grow" and flag["new_stage"] == "s-new"
    assert resize_rec.read_resize_flag(memkv, "j", "other") is None

    resize_rec.write_go(memkv, "j", "s-old", "s-new", "grow")
    go = resize_rec.read_go(memkv, "j", "s-old")
    assert go["new_stage"] == "s-new" and go["mode"] == "grow"

    resize_rec.publish_world_service(memkv, "j", "s-new",
                                     "10.0.0.1:4242", 3)
    svc = resize_rec.read_world_service(memkv, "j", "s-new")
    assert svc["endpoint"] == "10.0.0.1:4242" and svc["world"] == 3
    assert resize_rec.read_world_service(memkv, "j", "s-old") is None

    resize_rec.write_done(memkv, "j", "s-new", "pod-a",
                          {"mode": "grow", "seconds": 1.5})
    resize_rec.write_done(memkv, "j", "s-new", "pod-b")
    done = resize_rec.load_done(memkv, "j", "s-new")
    assert set(done) == {"pod-a", "pod-b"}
    assert done["pod-a"]["seconds"] == 1.5


def test_collect_shard_map_counts_owner_sets_once(memkv):
    """The shard map feeding the plan counts only owner-held sets — a
    ring replica is a copy of the same keys, not extra bytes."""
    from edl_tpu.memstate import advert
    from edl_tpu.memstate.reshard import collect_shard_map
    from edl_tpu.memstate.service import StateCacheService
    from edl_tpu.rpc.server import RpcServer

    servers = []
    regs = []
    try:
        for pod in ("pod-a", "pod-b"):
            svc = StateCacheService(memkv, "j", pod)
            srv = RpcServer("127.0.0.1", 0)
            srv.register_instance(svc)
            srv.start()
            servers.append((pod, svc, srv))
            regs.append(advert.advertise(memkv, "j", pod,
                                         f"127.0.0.1:{srv.port}", ttl=30))
        # pod-a owns a 2-shard set at step 7; pod-b holds a replica of
        # it plus its own 1-shard set
        import zlib
        for pod, svc, _srv in servers:
            owners = {"pod-a": [("k1", b"abcd"), ("k2", b"efgh")]}
            if pod == "pod-b":
                owners["pod-b"] = [("k3", b"ij")]
            for owner, blobs in owners.items():
                for key, data in blobs:
                    svc.cache_put_chunk(owner, 7, key, 0, data, True)
                svc.cache_commit(owner, 7, {
                    key: {"crc": zlib.crc32(data), "nbytes": len(data),
                          "dtype": "uint8", "shape": [len(data)],
                          "index": [[0, len(data)]],
                          "gshape": [len(data)], "leaf": key}
                    for key, data in blobs})
        advert.write_committed_step(memkv, "j", 7)
        shard_map = collect_shard_map(memkv, "j")
        assert set(shard_map) == {"k1", "k2", "k3"}
        assert shard_map["k1"]["owner"] == "pod-a"
        assert shard_map["k3"] == {"owner": "pod-b", "nbytes": 2}
    finally:
        for r in regs:
            r.stop()
        for _pod, _svc, srv in servers:
            srv.stop()


def test_host_world_service_binds_and_publishes(memkv):
    """The leader launcher's half of the resizable world: bind a jax
    coordination service on a fresh port and publish it under
    ``worldsvc/<stage>``.  Every multi-pod job passes through here, so
    an import or signature that the installed jaxlib does not have
    fails this test instead of every world formation."""
    import socket

    from edl_tpu.train.distributed import host_world_service

    service = host_world_service(memkv, "job", "stage-1", 2, "127.0.0.1")
    try:
        rec = resize_rec.read_world_service(memkv, "job", "stage-1")
        assert rec["world"] == 2 and rec["ts"] > 0
        host, port = rec["endpoint"].rsplit(":", 1)
        assert host == "127.0.0.1"
        with socket.create_connection((host, int(port)), timeout=5):
            pass                      # the service really listens there
    finally:
        service.shutdown()
