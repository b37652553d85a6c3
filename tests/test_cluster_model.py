"""Cluster model: serialization round-trips, rank renumbering, the env
ABI, status tables, train state (reference test_pod.py/test_cluster.py/
test_state.py)."""

from edl_tpu.cluster.cluster import Cluster
from edl_tpu.cluster.env import JobEnv, TrainerEnv, trainer_env_vars
from edl_tpu.cluster.pod import Pod
from edl_tpu.cluster.state import AdjustRegistry, State
from edl_tpu.cluster.status import Status, load_job_status, load_pods_status, save_job_status, save_pod_status
from edl_tpu.cluster.train_status import TrainStatus, load_train_status, save_train_status


def make_pod(addr="10.0.0.1", nproc=2, devices=(0, 1)):
    pod = Pod(addr=addr, port=9000, device_ids=list(devices))
    pod.make_trainers(nproc, [9100 + i for i in range(nproc)])
    return pod


def test_pod_roundtrip_and_device_split():
    pod = make_pod(nproc=2, devices=(0, 1, 2, 3))
    assert [t.device_ids for t in pod.trainers] == [[0, 1], [2, 3]]
    pod.rank = 3
    pod2 = Pod().from_json(pod.to_json())
    assert pod2 == pod
    assert pod2.rank == 3
    assert pod2.trainers[1].endpoint == pod.trainers[1].endpoint


def test_cluster_global_ranks_and_stage():
    pods = [make_pod(f"10.0.0.{i}") for i in range(3)]
    c = Cluster.from_pods(pods)
    assert [p.rank for p in c.pods] == [0, 1, 2]
    assert [t.global_rank for p in c.pods for t in p.trainers] == list(range(6))
    assert c.world_size == 6
    assert c.leader.pod_id == pods[0].pod_id
    assert len(c.get_trainers_endpoints()) == 6

    c2 = Cluster().from_json(c.to_json())
    assert c2 == c and c2.same_membership(c)

    # membership change ⇒ new stage ⇒ not same_membership
    c3 = Cluster.from_pods(pods[:2])
    assert not c3.same_membership(c)


def test_cluster_store_roundtrip_guarded(memkv):
    c = Cluster.from_pods([make_pod()])
    memkv.put("/edl_tpu/j1/rank/0", b"boss")
    c.save_to_store(memkv, "j1", "boss")
    got = Cluster.load_from_store(memkv, "j1")
    assert got == c
    # non-leader write refused
    import pytest
    from edl_tpu.utils.exceptions import EdlTableError
    with pytest.raises(EdlTableError):
        c.save_to_store(memkv, "j1", "impostor")


def test_trainer_env_abi():
    pods = [make_pod("10.0.0.1"), make_pod("10.0.0.2")]
    cluster = Cluster.from_pods(pods)

    class _A:
        job_id = "j1"
        coord_endpoints = "h:2379"

    env = trainer_env_vars(JobEnv(_A()), pods[1], pods[1].trainers[1], cluster)
    te = TrainerEnv(env)
    assert te.job_id == "j1"
    assert te.global_rank == 3 and te.rank_in_pod == 1
    assert te.world_size == 4 and len(te.trainer_endpoints) == 4
    assert te.coordinator == cluster.get_trainers_endpoints()[0]
    assert te.endpoint == pods[1].trainers[1].endpoint
    assert te.pod_rank == 1 and te.cluster_stage == cluster.stage
    assert te.is_distributed


def test_trainer_env_narrows_a_trainer_to_its_chips():
    """``--devices`` must reach libtpu before the child imports jax: a
    chip belongs to one process, and EDL_TPU_DEVICE_IDS alone narrows
    nothing.  A lone trainer on a row of the 2x2 host gets the measured
    layout; a trainer that owns the whole host gets nothing; trainers
    that would share one host's chips are refused (ROADMAP S9c)."""
    import pytest

    class _A:
        job_id = "j1"
        coord_endpoints = "h:2379"

    def env_of(pods, i=0):
        return trainer_env_vars(JobEnv(_A()), pods[i], pods[i].trainers[0],
                                Cluster.from_pods(pods))

    env = env_of([make_pod("10.0.0.1", nproc=1, devices=(2, 3))])
    assert env["EDL_TPU_DEVICE_IDS"] == env["TPU_VISIBLE_CHIPS"] == "2,3"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["ALLOW_MULTIPLE_LIBTPU_LOAD"] == "1"

    env = env_of([make_pod(nproc=1, devices=())])
    assert not [k for k in env if k.startswith("TPU_")]

    pair = [make_pod("10.0.0.1", nproc=1, devices=(0, 1)),
            make_pod("10.0.0.1", nproc=1, devices=(2, 3))]
    with pytest.raises(ValueError, match="share one host's chips"):
        env_of(pair, 1)
    for bad in ((0, 1, 2), (0, 2), (1, 2)):     # not a row of the host
        with pytest.raises(ValueError, match="not a chip set"):
            env_of([make_pod("10.0.0.1", nproc=1, devices=bad)])


def test_status_tables(memkv):
    save_pod_status(memkv, "j", "p0", Status.RUNNING)
    save_pod_status(memkv, "j", "p1", Status.FAILED)
    assert load_pods_status(memkv, "j") == {"p0": Status.RUNNING, "p1": Status.FAILED}
    save_job_status(memkv, "j", Status.SUCCEED)
    assert load_job_status(memkv, "j") == Status.SUCCEED
    save_train_status(memkv, "j", "p0", TrainStatus.NEARTHEEND)
    assert load_train_status(memkv, "j", "p0") == TrainStatus.NEARTHEEND
    # reference defect fixed: NEARTHEEND and SUCCEED are distinct
    assert TrainStatus.NEARTHEEND != TrainStatus.SUCCEED


def test_state_epochs_data_checkpoint_and_adjust(memkv):
    s = State(total_batch_size=1024, user_defined={"lr": 0.1})
    s.record_epoch(0, world_size=8, step_num=100, avg_step_time=0.5)
    s.record_epoch(1, world_size=6, step_num=120, avg_step_time=0.6)
    s.data_checkpoint.reader_name = "imagenet"
    s.data_checkpoint.file_list = ["a.rec", "b.rec"]
    s.data_checkpoint.mark_processed(0, 0, 100)
    s.data_checkpoint.mark_processed(0, 100, 200)  # merges -> [0,200)
    s.data_checkpoint.mark_processed(1, 50, 60)

    s.save_to_store(memkv, "j", "imagenet")
    s2 = State.load_from_store(memkv, "j", "imagenet")
    assert s2 == s
    assert s2.next_epoch == 2
    assert len(s2.data_checkpoint.processed) == 2
    assert s2.data_checkpoint.is_processed(0, 150)
    assert not s2.data_checkpoint.is_processed(0, 200)
    assert s2.epoch_attr(1).world_size == 6

    adj = AdjustRegistry()
    calls = []
    adj.register(lambda old, new, st: calls.append((old, new)))
    adj.run(8, 8, s2)
    assert calls == []
    adj.run(8, 6, s2)
    assert calls == [(8, 6)]
