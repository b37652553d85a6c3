"""Blockwise fused cross-entropy == dense log_softmax CE (value + grads)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.ops.ce import blockwise_cross_entropy
from tests.helpers.meshes import mesh_of as _mesh


def _dense_nll(hidden, weight, targets):
    logits = (hidden @ weight).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


@pytest.mark.parametrize("V,block", [(1000, 256), (512, 512), (300, 1024)])
def test_forward_matches_dense(V, block):
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(17, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, V)), jnp.float32)
    t = jnp.asarray(rng.integers(0, V, (17,)), jnp.int32)
    got = blockwise_cross_entropy(h, w, t, block_size=block)
    np.testing.assert_allclose(got, _dense_nll(h, w, t), rtol=1e-5, atol=1e-5)


def test_grads_match_dense():
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(11, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 700)), jnp.float32)
    t = jnp.asarray(rng.integers(0, 700, (11,)), jnp.int32)

    def fused(h, w):
        return blockwise_cross_entropy(h, w, t, block_size=128).mean()

    def dense(h, w):
        return _dense_nll(h, w, t).mean()

    gh_f, gw_f = jax.grad(fused, argnums=(0, 1))(h, w)
    gh_d, gw_d = jax.grad(dense, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(gh_f, gh_d, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gw_f, gw_d, rtol=1e-5, atol=1e-6)


def test_leading_dims_and_jit():
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(2, 5, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 96)), jnp.float32)
    t = jnp.asarray(rng.integers(0, 96, (2, 5)), jnp.int32)
    got = jax.jit(lambda h, w, t: blockwise_cross_entropy(
        h, w, t, block_size=32))(h, w, t)
    assert got.shape == (2, 5)
    np.testing.assert_allclose(got, _dense_nll(h, w, t), rtol=1e-5, atol=1e-5)


def test_bf16_hidden_runs_close():
    rng = np.random.default_rng(3)
    h32 = jnp.asarray(rng.normal(size=(9, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
    t = jnp.asarray(rng.integers(0, 256, (9,)), jnp.int32)
    got = blockwise_cross_entropy(h32.astype(jnp.bfloat16),
                                  w.astype(jnp.bfloat16), t, block_size=64)
    ref = _dense_nll(h32, w, t)
    np.testing.assert_allclose(got, ref, rtol=0.1, atol=0.15)

    g = jax.grad(lambda h: blockwise_cross_entropy(
        h, w.astype(jnp.bfloat16), t, block_size=64).mean())(
        h32.astype(jnp.bfloat16))
    assert g.dtype == jnp.bfloat16 and np.isfinite(
        np.asarray(g, np.float32)).all()


def test_mismatched_shapes_raise():
    h = jnp.zeros((4, 8))
    w = jnp.zeros((8, 32))
    t = jnp.zeros((5,), jnp.int32)
    with pytest.raises(ValueError):
        blockwise_cross_entropy(h, w, t)


def test_transformer_fused_loss_matches_dense():
    """lm_loss(model logits) == lm_loss_fused(hidden) — values and grads."""
    from edl_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss, lm_loss_fused,
    )

    cfg = TransformerConfig(vocab_size=97, num_layers=2, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=16,
                            dtype=jnp.float32, attention_impl="dense",
                            remat=False)
    model = TransformerLM(cfg)
    rng = np.random.default_rng(5)
    ids = jnp.asarray(rng.integers(0, 97, (3, 12)), jnp.int32)
    params = model.init(jax.random.key(0), ids)["params"]

    def dense(p):
        return lm_loss(model.apply({"params": p}, ids[:, :-1]), ids[:, 1:])

    def fused(p):
        h = model.apply({"params": p}, ids[:, :-1], return_hidden=True)
        return lm_loss_fused(p, h, ids[:, 1:], cfg, block_size=32)

    np.testing.assert_allclose(dense(params), fused(params),
                               rtol=1e-5, atol=1e-6)
    gd = jax.grad(dense)(params)
    gf = jax.grad(fused)(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-4, atol=1e-5), gd, gf)


def test_transformer_fused_loss_tied_embeddings():
    from edl_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss, lm_loss_fused,
    )

    cfg = TransformerConfig(vocab_size=64, num_layers=1, embed_dim=16,
                            num_heads=2, mlp_dim=32, max_len=8,
                            dtype=jnp.float32, attention_impl="dense",
                            remat=False, tie_embeddings=True)
    model = TransformerLM(cfg)
    ids = jnp.asarray(np.random.default_rng(6).integers(0, 64, (2, 8)),
                      jnp.int32)
    params = model.init(jax.random.key(1), ids)["params"]

    def dense(p):
        return lm_loss(model.apply({"params": p}, ids[:, :-1]), ids[:, 1:])

    def fused(p):
        h = model.apply({"params": p}, ids[:, :-1], return_hidden=True)
        return lm_loss_fused(p, h, ids[:, 1:], cfg, block_size=16)

    np.testing.assert_allclose(fused(params), dense(params),
                               rtol=1e-5, atol=1e-6)
    # the tied path routes the head grad back into tok_embed — compare
    # the full grad trees, not just values
    gd = jax.grad(dense)(params)
    gf = jax.grad(fused)(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-4, atol=1e-5), gd, gf)


# -- the token sweep (``ops/ce.py:sweep_cross_entropy``), which
# ``lm_loss_fused`` takes where the head's weight is whole ---------------

def _head(D, V, tied=False, dtype=jnp.float32, **kw):
    """``(cfg, params)`` of a bare head: the two leaves ``lm_loss_fused``
    reads, float32 as the model keeps them."""
    from edl_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=V, num_layers=1, embed_dim=D,
                            num_heads=2, mlp_dim=2 * D, max_len=16,
                            dtype=dtype, tie_embeddings=tied, **kw)
    w = jnp.asarray(np.random.default_rng(7).normal(size=(D, V)) / D ** 0.5,
                    jnp.float32)
    return cfg, ({"tok_embed": {"embedding": w.T}} if tied
                 else {"lm_head": {"kernel": w}})


def _dense_loss(params, hidden, targets, cfg, mask):
    """``lm_loss`` of the dense head as ``TransformerLM`` computes it, but
    for the logits' dtype: float32 from the matmul's accumulator."""
    from edl_tpu.models.transformer import lm_loss

    w = (params["tok_embed"]["embedding"].T if cfg.tie_embeddings
         else params["lm_head"]["kernel"])
    logits = jnp.einsum("bld,dv->blv", hidden, w.astype(hidden.dtype),
                        preferred_element_type=jnp.float32)
    return lm_loss(logits / cfg.logits_scaling, targets, mask)


SWEEPS = {
    "plain": {},
    "mask": dict(mask=True),
    "logits_scaling": dict(logits_scaling=3.0),
    "tied_head": dict(tied=True),
    "vocab_no_block_width_divides": dict(V=97),
    "tokens_the_block_does_not_divide": dict(L=11),
    "one_block": dict(block_rows=16),
    "bf16": dict(dtype=jnp.bfloat16),
    "cotangent": dict(cotangent=-2.5),
    "all_at_once": dict(mask=True, logits_scaling=3.0, tied=True, V=97, L=11,
                        dtype=jnp.bfloat16, cotangent=-2.5),
}


@pytest.mark.parametrize("case", sorted(SWEEPS))
def test_sweep_matches_the_dense_head(case, monkeypatch):
    """Where the head is whole ``lm_loss_fused`` is the token sweep: its
    value, and its gradients in the hidden states, the head's weight and
    the mask, against the dense head's, over blocks of 4 rows of the
    sequence (``L = 12`` is three of them, 11 leaves a tail that is
    padded); and the call that is not differentiated, which forms no
    gradient, returns the same value."""
    from edl_tpu.models.transformer import lm_loss_fused
    from edl_tpu.ops import ce

    c = dict(dict(mask=False, logits_scaling=1.0, tied=False, V=64, L=12,
                  block_rows=4, dtype=jnp.float32, cotangent=1.0),
             **SWEEPS[case])
    B, D, V, L = 3, 16, c["V"], c["L"]
    monkeypatch.setattr(ce, "SWEEP_LOGITS_BYTES", 4 * V * B * c["block_rows"])
    assert ce._token_blocks(B, L, V) == (-(-L // c["block_rows"]),
                                         min(c["block_rows"], L))
    cfg, params = _head(D, V, c["tied"], c["dtype"],
                        logits_scaling=c["logits_scaling"])
    rng = np.random.default_rng(8)
    hidden = jnp.asarray(rng.normal(size=(B, L, D)), c["dtype"])
    targets = jnp.asarray(rng.integers(0, V, (B, L)), jnp.int32)
    mask = (jnp.asarray(rng.integers(0, 2, (B, L)), jnp.float32)
            if c["mask"] else None)

    def scaled(loss):
        return lambda *a: c["cotangent"] * loss(*a, targets, cfg, mask)

    def fused(p, h, t, cfg, m):
        return lm_loss_fused(p, h, t, cfg, mask=m)

    args = (params, hidden)
    got, got_g = jax.value_and_grad(scaled(fused), argnums=(0, 1))(*args)
    want, want_g = jax.value_and_grad(scaled(_dense_loss),
                                      argnums=(0, 1))(*args)
    # bf16: the two round the same operands (but for hidden / 3, which
    # the fused loss rounds to bf16 where the dense head divides its
    # float32 logits), and differ by the order of the float32 sums and
    # one bf16 rounding of dlogits
    f32 = c["dtype"] == jnp.float32
    tol = dict(rtol=1e-5, atol=1e-6) if f32 else dict(rtol=2e-2, atol=2e-3)
    np.testing.assert_allclose(got, want, rtol=1e-5 if f32 else 1e-3)
    np.testing.assert_allclose(scaled(fused)(*args), got, rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), **tol),
        got_g, want_g)
    assert got_g[1].dtype == hidden.dtype
    if mask is not None:
        got_m, want_m = (jax.grad(
            lambda m, f=f: c["cotangent"] * f(params, hidden, targets, cfg,
                                              m))(mask)
            for f in (fused, _dense_loss))
        np.testing.assert_allclose(got_m, want_m, **(
            dict(rtol=1e-4, atol=1e-6) if f32 else tol))


@pytest.mark.parametrize("axes", [dict(dp=4), dict(dp=2, sp=2)],
                         ids=["dp4", "dp2_sp2"])
def test_sweep_on_a_mesh_is_the_sweep_of_every_devices_own_rows(axes,
                                                                monkeypatch):
    """On a mesh that splits the rows alone (the batch, the sequence)
    the sweep runs under ``shard_map``, each device over its own
    ``[B / dp, L / sp]`` rows in blocks it sizes from them, and the loss
    and the head's gradient are summed over the devices: the unsharded
    sweep's value and gradients."""
    import dataclasses

    from edl_tpu.models.transformer import lm_loss_fused
    from edl_tpu.ops import ce

    B, L, D, V = 4, 8, 16, 64
    monkeypatch.setattr(ce, "SWEEP_LOGITS_BYTES", 4 * V * 2)
    cfg, params = _head(D, V)
    rng = np.random.default_rng(9)
    hidden = jnp.asarray(rng.normal(size=(B, L, D)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, V, (B, L)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (B, L)), jnp.float32)

    def run(cfg):
        return jax.jit(jax.value_and_grad(
            lambda p, h: lm_loss_fused(p, h, targets, cfg, mask=mask),
            argnums=(0, 1)))(params, hidden)

    want, want_g = run(cfg)
    got, got_g = run(dataclasses.replace(cfg, mesh=_mesh(**axes)))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-5, atol=1e-7), got_g, want_g)


def _head_matmuls_by_loop(jaxpr, loops=None, inside=None):
    """``dot_general``s of ``jaxpr`` by the loop that holds them, loops in
    program order: ``[3]`` is one loop of three, ``[1, 3]`` two loops;
    a matmul outside any loop counts under ``None``'s entry, first."""
    if loops is None:
        loops = [0]
        inside = 0
    for eqn in jaxpr.eqns:
        here = inside
        if eqn.primitive.name in ("scan", "while"):
            loops.append(0)
            here = len(loops) - 1
        if eqn.primitive.name == "dot_general":
            loops[here] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _head_matmuls_by_loop(sub, loops, here)
    return loops


@pytest.mark.parametrize("where,expected", [
    ("no_mesh", [3]),
    ("mesh_of_one", [3]),
    ("mesh_dp4", [3]),
    ("mesh_dp2_sp2", [3]),
    ("mesh_fsdp4", [1, 3]),
    ("mesh_dp2_tp2", [1, 3])])
def test_the_head_is_multiplied_three_times_where_it_is_whole(where,
                                                              expected):
    """The differentiated ``lm_loss_fused`` multiplies by the head's
    weight three times (logits, ``dhidden``, ``dW``), all in the one
    forward sweep, where the weight lies whole on every device: without
    a mesh, on a mesh of one, on a mesh that splits the batch or the
    sequence alone.  Where an axis of the mesh splits ``embed`` or
    ``vocab`` (``fsdp``, ``tp``) it keeps the loop over blocks of the
    vocabulary, whose compiled step must stay what the ledger measured:
    one matmul a block forward, three backward (the logits again).  The
    function here is the head alone, so every ``dot_general`` is one."""
    import dataclasses

    from edl_tpu.models import transformer as tf_mod

    cfg, params = _head(16, 64)
    cfg = dataclasses.replace(cfg, mesh={
        "no_mesh": lambda: None,
        "mesh_of_one": _mesh,
        "mesh_dp4": lambda: _mesh(dp=4),
        "mesh_dp2_sp2": lambda: _mesh(dp=2, sp=2),
        "mesh_fsdp4": lambda: _mesh(fsdp=4),
        "mesh_dp2_tp2": lambda: _mesh(dp=2, tp=2),
    }[where]())
    hidden = jnp.zeros((4, 8, 16), jnp.float32)
    targets = jnp.zeros((4, 8), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, h: tf_mod.lm_loss_fused(p, h, targets, cfg, block_size=32),
        argnums=(0, 1)))(params, hidden)
    outside, *loops = _head_matmuls_by_loop(jaxpr.jaxpr)
    assert outside == 0 and loops == expected
    assert tf_mod._head_is_whole(cfg) == (expected == [3])
    scope = "ce/sweep" if expected == [3] else "ce/vocab_blocks"
    text = jaxpr.pretty_print(name_stack=True)
    assert scope in text and ("ce/sweep" in text) != (
        "ce/vocab_blocks" in text)
