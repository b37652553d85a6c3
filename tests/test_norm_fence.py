"""The fence around a block's norms (``transformer._fences_norms``,
PERF.md section 6, PR 47): where it stands, and that it is the identity.
The compiled one-chip train step that it is for is held in
``tests/test_decode_attention.py`` (the file with the described chip).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models import transformer as tf_mod
from edl_tpu.models.transformer import (TransformerConfig, TransformerLM,
                                        lm_loss_fused)
from tests.helpers.meshes import mesh_of as _mesh

CFG = TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                        num_heads=4, num_kv_heads=2, mlp_dim=64, max_len=16,
                        dtype=jnp.float32, attention_impl="dense", remat=True,
                        scan_layers=False)
STACKS = {
    "unrolled_remat": {},
    "scan_layers": dict(scan_layers=True),
    "no_remat": dict(remat=False),
    "moe": dict(moe_experts=4, moe_top_k=2, moe_capacity=4.0),
    "moe_dropless_every_other": dict(
        moe_experts=4, moe_top_k=2, moe_capacity=0.0, moe_gated=True,
        layer_mlp=("dense", "sparse"), remat=False),
    "ssm_hybrid": dict(layer_attn=("ssm", "global"), ssm_heads=4,
                       ssm_head_dim=16, ssm_state=8, ssm_groups=1,
                       remat=False),
    "layer_norm": dict(norm="layer"),
}


def _barriers(cfg) -> int:
    model = TransformerLM(cfg)
    ids = jnp.zeros((4, 8), jnp.int32)
    variables = jax.eval_shape(lambda: model.init(jax.random.key(0), ids))
    mutable = ["cache"] if cfg.decode else False
    return str(jax.make_jaxpr(
        lambda v: model.apply(v, ids, mutable=mutable))(variables)).count(
            "optimization_barrier")


def _loss_and_grads(cfg):
    model = TransformerLM(cfg)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 64, (2, 9)),
                      jnp.int32)
    params = model.init(jax.random.key(1), ids[:, :-1])["params"]

    def loss(p):
        h, aux = model.apply({"params": p}, ids[:, :-1], return_hidden=True,
                             with_aux=True)
        return lm_loss_fused(p, h, ids[:, 1:], cfg, block_size=8) + aux

    return jax.jit(jax.value_and_grad(loss))(params)


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_fenced_model_is_the_unfenced_model(stack, monkeypatch):
    """The barrier is the identity and has a differentiation rule: the
    loss of a fenced stack (under remat, scanned for real, expert and
    state-space blocks, LayerNorm) equals that of the same stack with
    the rule turned off bit for bit, and every gradient to 32 ulps of
    its leaf's largest element (the seven stacks read 2 to 8 here).  Not
    bit for bit: the residual stream feeds a norm and the residual add,
    so its cotangent is a sum of three terms (two from the norm), and
    the fence makes the norm's two meet first; run op by op
    (``jax.disable_jit``) the two sides differ by that re-association
    alone (a few 1e-8 at these sizes)."""
    cfg = dataclasses.replace(CFG, **STACKS[stack])
    assert tf_mod._fences_norms(cfg) and _barriers(cfg) > 0
    loss, grads = _loss_and_grads(cfg)
    monkeypatch.setattr(tf_mod, "_fences_norms", lambda cfg: False)
    assert _barriers(cfg) == 0
    want_loss, want = _loss_and_grads(cfg)
    assert np.isfinite(float(loss)) and float(loss) == float(want_loss)
    flat, tree = jax.tree.flatten(grads)
    want_flat, want_tree = jax.tree.flatten(want)
    assert tree == want_tree
    assert any(np.abs(g).max() > 0 for g in flat)
    for g, w in zip(flat, want_flat):
        ulp = np.spacing(np.abs(np.asarray(w)).max())
        assert np.abs(np.asarray(g) - np.asarray(w)).max() <= 32 * ulp


@pytest.mark.parametrize("where,expected", [
    ("no_mesh", 2 * (2 + 1)),     # a uniform stack's body is traced once
    ("no_mesh_differing_layers", 2 * (2 * CFG.num_layers + 1)),
    ("mesh_of_one", 2 * (2 + 1)),
    ("mesh_dp4", 2 * (2 + 1)),
    ("mesh_fsdp4", 0),
    ("mesh_dp2_tp2", 0),
    ("decode", 0),
    ("decode_on_mesh_of_one", 0)])
def test_fence_stands_where_the_layer_weights_are_whole(where, expected):
    """Two barriers a norm (``attn_norm``, ``mlp_norm``, ``final_norm``)
    in a model that is differentiated with its layer weights whole on
    every device: without a mesh, on a mesh of one, on a mesh that
    splits the batch alone.  None where an axis of the mesh splits the
    weights (``fsdp``, ``tp``), whose compiled step holds no matmul with
    a norm's reduction and must stay what the ledger measured, and none
    in a decode model."""
    cfg = dataclasses.replace(CFG, **{
        "no_mesh": {},
        "no_mesh_differing_layers": dict(layer_attn=("window", "global"),
                                         attn_window=4),
        "mesh_of_one": dict(mesh=_mesh()),
        "mesh_dp4": dict(mesh=_mesh(dp=4)),
        "mesh_fsdp4": dict(mesh=_mesh(fsdp=4)),
        "mesh_dp2_tp2": dict(mesh=_mesh(dp=2, tp=2)),
        "decode": dict(decode=True, remat=False),
        "decode_on_mesh_of_one": dict(decode=True, remat=False,
                                      mesh=_mesh()),
    }[where])
    assert _barriers(cfg) == expected
    assert tf_mod._fences_norms(cfg) == (expected > 0)
