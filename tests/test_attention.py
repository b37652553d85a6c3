"""Attention dispatch and numerics (edl_tpu/ops/attention.py).

The pallas kernels (splash/flash) only exist on TPU; CPU covers the
dense path plus the dispatch decisions themselves.  TPU-only parity
tests are gated on the platform so the same file runs everywhere.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.ops.attention import (
    _splash_ok, dense_attention, dot_product_attention,
)


def _ref_attention(q, k, v, causal):
    """O(L^2) numpy reference, f64 softmax."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    logits = np.einsum("bqhd,bkhd->bhqk", np.float64(q), np.float64(k))
    logits *= D ** -0.5
    if causal:
        mask = np.tril(np.ones((Lq, Lk), bool), k=Lk - Lq)
        logits = np.where(mask[None, None], logits, -np.inf)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", w, np.float64(v))


@pytest.mark.parametrize("causal", [False, True])
def test_dense_matches_reference(causal):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
               for _ in range(3))
    out = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), _ref_attention(q, k, v, causal),
                               atol=1e-5)


def test_dense_grouped_kv_matches_repeat():
    # GQA: grouped einsum == explicit kv-head repetition
    rng = np.random.default_rng(3)
    H, Hk = 6, 2
    q = jnp.asarray(rng.normal(size=(2, 16, H, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 16, Hk, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 16, Hk, 8)), jnp.float32)
    grouped = dense_attention(q, k, v, causal=True)
    repeated = dense_attention(q, jnp.repeat(k, H // Hk, axis=2),
                               jnp.repeat(v, H // Hk, axis=2), causal=True)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(repeated),
                               atol=1e-5)


def test_dense_grouped_kv_batched_mask():
    # a [B, 1, Lq, Lk] mask must broadcast identically in the GQA and
    # MHA branches (it used to meet 5-D grouped logits: shape error, or
    # silent mis-masking when B == Hk)
    rng = np.random.default_rng(5)
    B, L, H, Hk = 2, 8, 4, 2      # B == Hk: the silent mis-mask case
    q = jnp.asarray(rng.normal(size=(B, L, H, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, L, Hk, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, L, Hk, 8)), jnp.float32)
    mask = jnp.asarray(rng.random((B, 1, L, L)) > 0.3)
    mask = mask | jnp.eye(L, dtype=bool)          # keep rows non-empty
    grouped = dense_attention(q, k, v, mask=mask)
    repeated = dense_attention(q, jnp.repeat(k, H // Hk, axis=2),
                               jnp.repeat(v, H // Hk, axis=2), mask=mask)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(repeated),
                               atol=1e-5)


def test_auto_on_cpu_is_dense():
    # no pallas kernels off-TPU: auto must resolve to dense and agree
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
               for _ in range(3))
    a = dot_product_attention(q, k, v, causal=True, impl="auto")
    d = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(d), atol=1e-6)


def test_splash_gate_shapes():
    def qk(L, D, Lk=None):
        q = jnp.zeros((1, L, 2, D))
        k = jnp.zeros((1, Lk if Lk else L, 2, D))
        return q, k

    assert _splash_ok(*qk(1024, 128), causal=True)
    assert _splash_ok(*qk(256, 64), causal=True)
    assert not _splash_ok(*qk(1024, 128), causal=False)   # causal-only
    assert not _splash_ok(*qk(100, 128), causal=True)     # L % 128
    assert not _splash_ok(*qk(1024, 80), causal=True)     # D % 64
    assert not _splash_ok(*qk(1024, 128, Lk=512), causal=True)  # cross-attn


def test_splash_rejects_non_causal():
    q = jnp.zeros((1, 128, 2, 64))
    with pytest.raises(ValueError, match="causal-only"):
        dot_product_attention(q, q, q, causal=False, impl="splash")


@pytest.mark.skipif(jax.devices()[0].platform != "tpu",
                    reason="pallas TPU kernels")
def test_splash_under_remat_scan():
    """Regression: the memoised splash kernel must not capture tracers
    when first built inside flax's nn.remat-under-nn.scan trace — the
    cached kernel poisoned every later trace (UnexpectedTracerError)
    until construction was moved under ensure_compile_time_eval."""
    from edl_tpu.models import TransformerConfig, TransformerLM
    from edl_tpu.models.transformer import lm_loss

    from edl_tpu.ops.attention import _splash_kernel
    _splash_kernel.cache_clear()   # force a fresh IN-TRACE kernel build

    cfg = TransformerConfig(vocab_size=128, num_layers=2, embed_dim=256,
                            num_heads=2, mlp_dim=256, max_len=256,
                            remat=True)
    model = TransformerLM(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 257)),
                      jnp.int32)
    params = model.init(jax.random.key(0), ids[:1, :8])["params"]

    def loss(p):
        return lm_loss(model.apply({"params": p}, ids[:, :-1]), ids[:, 1:])

    g = jax.jit(jax.grad(loss))(params)
    assert np.isfinite(float(jax.tree.leaves(g)[0].astype(jnp.float32).sum()))


@pytest.mark.skipif(jax.devices()[0].platform != "tpu",
                    reason="pallas TPU kernels")
def test_splash_matches_dense_on_tpu():
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 256, 2, 128)), jnp.bfloat16)
               for _ in range(3))
    s = dot_product_attention(q, k, v, causal=True, impl="splash")
    d = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.float32(s), np.float32(d),
                               atol=2e-2, rtol=2e-2)


def test_splash_runs_per_shard_on_a_mesh(monkeypatch):
    """On a mesh the Mosaic call sits under shard_map over the batch
    (dp x fsdp) and head (tp) axes: each device's kernel sees only its
    own rows and heads, and the pieces reassemble to dense attention.
    The kernel itself is TPU-only, so a dense per-example stand-in with
    the kernel's [H, L, D] contract records the shapes it was built for."""
    from edl_tpu.ops import attention
    from edl_tpu.parallel import MeshSpec, build_mesh

    built = []

    def fake_kernel(L, H, blk):
        built.append((L, H))

        def kernel(q, k, v):            # [H, L, D]; q arrives pre-scaled
            out = dense_attention(q.swapaxes(0, 1)[None],
                                  k.swapaxes(0, 1)[None],
                                  v.swapaxes(0, 1)[None], causal=True,
                                  sm_scale=1.0)
            return out[0].swapaxes(0, 1)
        return kernel

    monkeypatch.setattr(attention, "_splash_kernel", fake_kernel)
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(8, 128, 4, 64)), jnp.float32)
               for _ in range(3))
    got = jax.jit(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True, impl="splash", mesh=mesh))(q, k, v)
    assert built == [(128, 2)]          # 4 heads over tp=2
    np.testing.assert_allclose(got, dense_attention(q, k, v, causal=True),
                               atol=1e-5, rtol=1e-5)
    assert got.sharding.spec == jax.sharding.PartitionSpec(
        ("dp", "fsdp"), None, "tp")
