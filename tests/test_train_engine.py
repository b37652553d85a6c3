"""Train engine: jitted DP/FSDP steps, checkpoint/resume, LR rules.

The linear-regression flow is the reference's fit_a_line smoke workload
(example/fit_a_line) run TPU-natively on the 8-device CPU mesh.
"""

import os

import numpy as np
import optax
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from edl_tpu.parallel import MeshSpec, ShardingRules
from edl_tpu.train import (
    CheckpointManager, ElasticTrainer, TrainConfig, TrainState,
    cosine_warmup, piecewise_decay, scale_lr_for_batch,
)
from edl_tpu.train.state import abstract_like

RNG = np.random.default_rng(0)
W_TRUE = RNG.normal(size=(13, 1)).astype(np.float32)


def make_batches(n_batches=8, bs=16):
    for _ in range(n_batches):
        x = RNG.normal(size=(bs, 13)).astype(np.float32)
        y = x @ W_TRUE + 0.01 * RNG.normal(size=(bs, 1)).astype(np.float32)
        yield {"x": x, "y": y}


def linear_loss(params, extra, batch, rng):
    pred = batch["x"] @ params["w"] + params["b"]
    loss = jnp.mean((pred - batch["y"]) ** 2)
    return loss, (extra, {"mse": loss})


def init_linear():
    return {"w": jnp.zeros((13, 1)), "b": jnp.zeros((1,))}, None


def make_trainer(tmp_path=None, spec=None, **cfg_kw):
    cfg = TrainConfig(mesh_spec=spec or MeshSpec(),
                      checkpoint_dir=str(tmp_path) if tmp_path else "",
                      log_every=0, **cfg_kw)
    return ElasticTrainer(linear_loss, cfg)


def test_fit_linear_regression_converges():
    tr = make_trainer()
    state = tr.create_state(init_linear, optax.sgd(0.1))
    state, meta = tr.fit(state, __import__("edl_tpu.cluster.state", fromlist=["State"]).State(),
                         lambda e: make_batches(30), epochs=2)
    w = np.asarray(state.params["w"])
    assert np.allclose(w, W_TRUE, atol=0.05)
    assert meta.next_epoch == 2
    assert len(meta.epochs) == 2 and meta.epochs[0].world_size == 8


def test_checkpoint_resume(tmp_path):
    tr = make_trainer(tmp_path)
    state, meta = tr.restore_or_create(init_linear, optax.sgd(0.1))
    assert meta.next_epoch == 0
    state, meta = tr.fit(state, meta, lambda e: make_batches(5), epochs=1)
    tr.ckpt.close()

    tr2 = make_trainer(tmp_path)
    state2, meta2 = tr2.restore_or_create(init_linear, optax.sgd(0.1))
    assert meta2.next_epoch == 1
    assert int(state2.step) == 5
    np.testing.assert_array_equal(np.asarray(state2.params["w"]),
                                  np.asarray(state.params["w"]))
    # resume continues into epoch 1 only
    state2, meta2 = tr2.fit(state2, meta2, lambda e: make_batches(5), epochs=2)
    assert int(state2.step) == 10
    assert [e.epoch_no for e in meta2.epochs] == [0, 1]
    tr2.ckpt.close()


def test_checkpoint_data_files_stay_under_the_file_size_limit(
        tmp_path, monkeypatch):
    """PR 21's chip check died with EFBIG in the first commit: OCDBT had
    batched the flagship's state into files past the machine's
    RLIMIT_FSIZE.  The writer now sizes its data files from that limit."""
    import resource

    from edl_tpu.train import checkpoint as ckpt_mod

    limit = 3 << 20
    monkeypatch.setattr(resource, "getrlimit", lambda which: (limit, limit))
    assert ckpt_mod._data_file_target() == limit // 3
    state = {"w": jnp.asarray(RNG.normal(size=(16, 256, 256)), jnp.float32),
             "step": jnp.zeros((), jnp.int32)}           # 4 MiB, one array
    ck = CheckpointManager(str(tmp_path))
    ck.save(1, state)
    ck.wait()
    sizes = [os.path.getsize(os.path.join(r, f))
             for r, _, fs in os.walk(tmp_path) for f in fs]
    assert sum(sizes) > limit > max(sizes)      # it had to be split
    got, meta = ck.restore(abstract_like(state))
    assert meta is None
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(state["w"]))
    ck.close()
    monkeypatch.setattr(
        resource, "getrlimit",
        lambda which: (resource.RLIM_INFINITY, resource.RLIM_INFINITY))
    assert ckpt_mod._data_file_target() == 32 << 20


def test_adjust_registry_fires_on_world_change(tmp_path):
    tr = make_trainer(tmp_path)
    state, meta = tr.restore_or_create(init_linear, optax.sgd(0.1))
    state, meta = tr.fit(state, meta, lambda e: make_batches(3), epochs=1)
    tr.ckpt.close()

    # resize: 8 -> 4 devices
    calls = []
    cfg = TrainConfig(mesh_spec=MeshSpec(dp=4), checkpoint_dir=str(tmp_path),
                      log_every=0)
    tr2 = ElasticTrainer(linear_loss, cfg, devices=jax.devices()[:4])
    tr2.adjust.register(lambda old, new, st: calls.append((old, new)))
    state2, meta2 = tr2.restore_or_create(init_linear, optax.sgd(0.1))
    assert calls == [(8, 4)]
    tr2.ckpt.close()


def test_fsdp_shards_params_and_momentum():
    spec = MeshSpec(dp=1, fsdp=8)
    cfg = TrainConfig(mesh_spec=spec, log_every=0)
    tr = ElasticTrainer(linear_loss, cfg)

    def init_big():
        return {"w": jnp.zeros((16, 8)), "b": jnp.zeros((8,))}, None

    logical = {"w": ("embed", None), "b": (None,)}
    state = tr.create_state(init_big, optax.adam(1e-3), param_logical=logical)
    assert state.params["w"].sharding.spec == P("fsdp")
    # optimizer momentum inherited the sharding through propagation
    mu_w = state.opt_state[0].mu["w"]
    assert mu_w.sharding.spec == P("fsdp")
    # and the step still runs
    batch = {"x": np.ones((8, 16), np.float32), "y": np.ones((8, 8), np.float32)}

    def loss(params, extra, b, rng):
        pred = b["x"] @ params["w"] + params["b"]
        l = jnp.mean((pred - b["y"]) ** 2)
        return l, (extra, {})
    tr2 = ElasticTrainer(loss, cfg)
    gb = __import__("edl_tpu.parallel.sharding", fromlist=["shard_host_batch"]
                    ).shard_host_batch(batch, tr.mesh)
    state2, metrics = tr2.step_fn(state, gb, jax.random.key(0))
    assert np.isfinite(float(metrics["loss"]))


def test_lr_schedules():
    assert scale_lr_for_batch(0.1, 1024) == pytest.approx(0.4)
    s = cosine_warmup(0.4, total_steps=100, warmup_steps=10)
    assert float(s(0)) == pytest.approx(0.0)
    assert float(s(10)) == pytest.approx(0.4)
    assert float(s(100)) < 0.01
    p = piecewise_decay(0.4, [30, 60], gamma=0.1, warmup_steps=5)
    assert float(p(5)) == pytest.approx(0.4)
    assert float(p(31)) == pytest.approx(0.04)
    assert float(p(61)) == pytest.approx(0.004)


def test_evaluate_masks_ragged_batches():
    """Per-example metrics over batches not divisible by the 8-way mesh:
    padding must be masked out exactly and jit compiled once."""
    tr = make_trainer()
    state = tr.create_state(init_linear, optax.sgd(0.1))

    def metric_fn(params, extra, batch):
        return {"v": batch["x"][:, 0]}

    vals = [np.arange(10, dtype=np.float32), np.arange(3, dtype=np.float32)]
    batches = [{"x": np.stack([v] * 13, axis=1)} for v in vals]
    out = tr.evaluate(state, batches, metric_fn)
    expect = float(np.concatenate(vals).mean())
    assert abs(out["v"] - expect) < 1e-6
    # second call reuses the cached jitted step (no retrace)
    out2 = tr.evaluate(state, batches, metric_fn)
    assert out2 == out
    assert len(tr._eval_cache) == 1


@pytest.mark.slow
def test_evaluate_uneven_batches_two_processes(tmp_path):
    """evaluate() must not hang when hosts yield different batch counts
    (per-batch has-next agreement; round-2 verdict weak #4).  Rank 0
    feeds 3 batches, rank 1 feeds 1; both must agree on the weighted
    mean over the 16 real rows."""
    import subprocess
    import sys
    import os as _os

    from edl_tpu.utils.network import find_free_port

    port = find_free_port()
    repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    script = _os.path.join(repo, "tests", "helpers", "eval_uneven.py")
    env = dict(_os.environ)
    env["PYTHONPATH"] = repo + _os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen([sys.executable, script, str(r), str(port)],
                              stdout=subprocess.PIPE, text=True, env=env)
             for r in (0, 1)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=180)
        assert p.returncode == 0, out
        outs.append(out)
    import json as _json
    results = []
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("EVAL_RESULT")][0]
        results.append(_json.loads(line.split(" ", 1)[1]))
    # expected: mean over rank0's 3 batches (12 rows) + rank1's 1 (4 rows)
    vals = [0 * 100 + b * 10 + i for b in range(3) for i in range(4)] + \
           [1 * 100 + 0 * 10 + i for i in range(4)]
    expected = sum(vals) / len(vals)
    for r in results:
        assert abs(r["mean_x"] - expected) < 1e-3, (results, expected)


def test_maybe_preempt_unit(memkv, monkeypatch):
    """Preempt check in isolation (single-process: WALL-CLOCK cadence,
    ADVICE r5): the first step checks, a step inside the cadence
    window never reads the store, and a due check with the flag
    visible checkpoints-and-exits PREEMPT_EXIT_CODE."""
    from edl_tpu.cluster import preempt
    from edl_tpu.cluster.env import TrainerEnv
    from edl_tpu.utils import constants

    monkeypatch.setenv("EDL_TPU_JOB_ID", "pj")
    monkeypatch.setenv("EDL_TPU_POD_ID", "pod1")
    monkeypatch.setenv("EDL_TPU_CLUSTER_STAGE", "stg")
    tenv = TrainerEnv()
    tr = ElasticTrainer(lambda *a: None, TrainConfig(log_every=0),
                        store=memkv, tenv=tenv)
    exits = []
    monkeypatch.setattr("os._exit", lambda code: exits.append(code))

    tr._maybe_preempt(None, None, 1)   # first call checks; no flag yet
    assert exits == []
    preempt.flag_preempt(memkv, "pj", "stg", "pod2")
    tr._maybe_preempt(None, None, 2)   # inside the window: no store read
    assert exits == []
    # force the cadence window to elapse without sleeping through it
    tr._preempt_last_check_t -= constants.PREEMPT_CHECK_SECONDS + 1
    tr._maybe_preempt(None, None, 3)   # due + flagged: exit
    assert exits == [constants.PREEMPT_EXIT_CODE]
