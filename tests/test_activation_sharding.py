"""Training activations live on the batch axes: ``models/transformer._pin``.

Weights carry ``embed -> fsdp``; an activation never does.  These tests
hold the training forward to that on the 8-device virtual CPU mesh:
the mathematics is the one-device program's on every mesh, a mesh of
one device (or none) traces to the program it always was, and the
compiled ``fsdp`` step moves no whole-batch activation between devices.
The same guard for the real widths, compiled for ``v5e:2x2``, sits in
``tests/test_decode_attention.py`` beside the other program compiled
for a described chip (one process may load libtpu).
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models.logical import logical_axes_from_paths
from edl_tpu.models.transformer import (LOGICAL_RULES, TransformerConfig,
                                        TransformerLM, lm_loss_fused)
from edl_tpu.parallel import MeshSpec, build_mesh
from edl_tpu.parallel.sharding import logical_sharding, tree_shardings
from tests.helpers.hlo import whole_batch_collectives

B, L, V, D, M, CE_BLOCK = 8, 32, 256, 64, 128, 64

MESHES = {
    "fsdp4": (MeshSpec(dp=1, fsdp=4), "dense"),
    "dp2-fsdp2": (MeshSpec(dp=2, fsdp=2), "dense"),
    "fsdp2-tp2": (MeshSpec(dp=1, fsdp=2, tp=2), "dense"),
    "fsdp2-sp2": (MeshSpec(dp=1, fsdp=2, sp=2), "ring"),
}


def _config(dtype, **kw) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=V, num_layers=2, embed_dim=D, num_heads=4, num_kv_heads=2,
        mlp_dim=M, max_len=L, dtype=dtype, attention_impl="dense",
        remat=True, scan_layers=False, **kw)


def _loss(cfg: TransformerConfig):
    lm = TransformerLM(cfg)

    def loss(params, ids):
        h = lm.apply({"params": params}, ids[:, :-1], return_hidden=True)
        return lm_loss_fused(params, h, ids[:, 1:], cfg, block_size=CE_BLOCK)

    return loss


def _inputs(cfg: TransformerConfig):
    ids = jax.random.randint(jax.random.key(1), (B, L + 1), 0, V)
    params = TransformerLM(cfg).init(jax.random.key(0), ids[:, :-1])["params"]
    return params, ids


@functools.cache
def _one_device(dtype):
    """Loss and gradients of the one-device program."""
    base = _config(dtype)
    return jax.jit(jax.value_and_grad(_loss(base)))(*_inputs(base))


def _on_mesh(cfg, name):
    spec, impl = MESHES[name]
    mesh = build_mesh(spec, jax.devices()[:math.prod(spec.sizes().values())])
    cfg = dataclasses.replace(cfg, mesh=mesh, attention_impl=impl)
    params, ids = _inputs(cfg)
    logical = logical_axes_from_paths(params, LOGICAL_RULES)
    params = jax.device_put(params, tree_shardings(logical, mesh))
    ids = jax.device_put(ids, logical_sharding(("batch", None), mesh))
    return cfg, params, ids


# f32: the same sums in another order.  bf16: the loss to the tolerance
# test_models.py holds a tp mesh's logits to, a gradient leaf to a few
# bf16 roundings (2**-8 each) of its largest entry: ring attention and a
# reduce-scatter sum the same bf16 products in another order, and a
# small entry carries the rounding of the large ones it was summed with
@pytest.mark.parametrize("dtype,tol,grad_tol", [
    (jnp.float32, 1e-5, 1e-5), (jnp.bfloat16, 2e-3, 5e-2)],
    ids=["f32", "bf16"])
@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_loss_and_gradients_equal_the_one_device_program(mesh_name, dtype,
                                                         tol, grad_tol):
    want_loss, want = _one_device(dtype)
    cfg, params, ids = _on_mesh(_config(dtype), mesh_name)
    loss, got = jax.jit(jax.value_and_grad(_loss(cfg)))(params, ids)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=tol)
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    for (path, w), g in zip(flat_want, jax.tree.leaves(got)):
        w, g = np.asarray(w, np.float32), np.asarray(g, np.float32)
        assert np.abs(g - w).max() <= grad_tol * np.abs(w).max(), (
            jax.tree_util.keystr(path))


@pytest.mark.parametrize("mesh_name", [None, "one-device"])
def test_one_device_traces_to_the_program_it_always_was(mesh_name):
    """No mesh, or a mesh of one device: no constraint is emitted, so
    the jaxpr is the one a model without ``_pin`` traces to (the step of
    the one-chip cell must not move)."""
    base = _config(jnp.float32)
    params, ids = _inputs(base)
    plain = str(jax.make_jaxpr(jax.value_and_grad(_loss(base)))(params, ids))
    assert "sharding_constraint" not in plain
    if mesh_name:
        mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
        cfg = dataclasses.replace(base, mesh=mesh)
        assert str(jax.make_jaxpr(jax.value_and_grad(_loss(cfg)))(
            params, ids)) == plain


def test_decode_model_on_a_mesh_emits_no_constraint():
    """The engine owns a decode model's layouts (tp serving)."""
    mesh = build_mesh(MeshSpec(dp=1, fsdp=2, tp=2), jax.devices()[:4])
    cfg = _config(jnp.float32, decode=True, mesh=mesh)
    lm = TransformerLM(cfg)
    ids = jnp.zeros((4, 8), jnp.int32)
    variables = lm.init(jax.random.key(0), ids)
    jaxpr = jax.make_jaxpr(lambda v, i: lm.apply(v, i, mutable=["cache"]))(
        variables, ids)
    assert "sharding_constraint" not in str(jaxpr)


def test_fsdp_step_moves_no_whole_batch_activation():
    """The compiled CPU step over fsdp=4, toy widths: with activations
    on the batch axes there is no all-reduce / all-gather / all-to-all of
    an activation of the whole batch (GSPMD's other choice: keep each
    weight's ``fsdp`` shard in place, gather the activations, all-reduce
    every matmul's product)."""
    cfg, params, ids = _on_mesh(_config(jnp.bfloat16), "fsdp4")
    hlo = jax.jit(jax.value_and_grad(_loss(cfg))).lower(
        params, ids).compile().as_text()
    assert whole_batch_collectives(hlo, B, L) == []
    # the guard has teeth: the same step with no mesh in the model's
    # config, as every trainer ran before, is full of them
    loose = dataclasses.replace(cfg, mesh=None)
    hlo = jax.jit(jax.value_and_grad(_loss(loose))).lower(
        params, ids).compile().as_text()
    assert whole_batch_collectives(hlo, B, L)
