"""Attention dispatch and numerics (edl_tpu/ops/attention.py).

The pallas kernels (splash/flash) only exist on TPU; CPU covers the
dense path plus the dispatch decisions themselves.  TPU-only parity
tests are gated on the platform so the same file runs everywhere.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.ops.attention import (
    _splash_ok, dense_attention, dot_product_attention, splash_block_sizes,
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


def _tiles(sizes):
    return (sizes.block_q, sizes.block_kv, sizes.block_kv_compute,
            sizes.block_q_dkv, sizes.block_kv_dkv,
            sizes.block_kv_dkv_compute, sizes.block_q_dq, sizes.block_kv_dq)


@pytest.mark.parametrize("window", [0, 128])
@pytest.mark.parametrize("L", [128, 384, 1024, 2048, 3072, 4096, 5120, 6144,
                               7168, 8192, 9216, 16384])
def test_splash_block_sizes_follow_the_shape(L, window):
    """The tiles are a pure function of the shape: every one divides
    the sequence (a compute tile its memory tile: the kernel refuses
    anything else), the backward's are all there, and anywhere but at a
    swept length without a window every tile is the largest of 512 /
    256 / 128 that divides it (what every shape ran before the sweep)."""
    from edl_tpu.ops.attention import _SWEPT_TILES, splash_partials_bytes
    sizes = splash_block_sizes(L, window)
    assert sizes == splash_block_sizes(L, window)
    assert sizes.has_backward_blocks
    tiles = [t for t in _tiles(sizes) if t is not None]
    assert all(L % t == 0 and t % 128 == 0 for t in tiles)
    assert sizes.block_kv % sizes.block_kv_compute == 0
    assert sizes.block_kv_dkv % sizes.block_kv_dkv_compute == 0
    # one kernel for the backward leaves no dq tiles to give, two do
    assert sizes.use_fused_bwd_kernel == (sizes.block_q_dq is None)
    assert sizes.use_fused_bwd_kernel == (L in _SWEPT_TILES and not window)
    if sizes.use_fused_bwd_kernel:
        # at most four partial dQs, and the estimate's buffer is theirs
        assert L // sizes.block_kv_dkv <= 4
        assert (splash_partials_bytes(3, L, 8, 128)
                == L // sizes.block_kv_dkv * 3 * 8 * L * 128 * 2)
    else:
        today = next(b for b in (512, 256, 128) if L % b == 0)
        assert _tiles(sizes) == (today,) * 8
        assert splash_partials_bytes(3, L, 8, 128, window) == 0


def _pallas_calls(jaxpr, times=1, out=None):
    """Pallas calls by kernel name in a jaxpr and everything under it,
    a scan's body counted once a trip."""
    out = {} if out is None else out
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            name = e.params["name"]
            out[name] = out.get(name, 0) + times
        inner = times * e.params.get("length", 1) \
            if e.primitive.name == "scan" else times
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    _pallas_calls(j, inner, out)
    return out


@pytest.mark.parametrize("seq", [256, 4096])
@pytest.mark.parametrize("stack", [
    dict(scan_layers=False), dict(scan_layers=True),
    dict(attn_window=128, layer_attn=("window", "global", "window")),
    dict(attn_window=128)],
    ids=["unrolled", "scan", "non-uniform", "window"])
def test_remat_runs_the_splash_forward_once_a_layer(stack, seq):
    """Traced, not run: the gradient of a remat stack holds ONE splash
    forward a layer (the kernel's ``out`` and logsumexp are kept by
    name beside the dots; with dots alone kept the backward pass ran
    the forward kernel a second time), and the backward kernels of the
    arrangement ``splash_block_sizes`` gives that shape."""
    from edl_tpu.models import TransformerConfig, TransformerLM
    from edl_tpu.models.transformer import lm_loss

    cfg = TransformerConfig(vocab_size=128, num_layers=3, embed_dim=256,
                            num_heads=2, mlp_dim=256, max_len=seq,
                            remat=True, attention_impl="splash", **stack)
    model = TransformerLM(cfg)
    ids = jax.ShapeDtypeStruct((1, seq + 1), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, seq), jnp.int32))["params"])

    def loss(p, ids):
        return lm_loss(model.apply({"params": p}, ids[:, :-1]), ids[:, 1:])

    got = _pallas_calls(jax.make_jaxpr(jax.grad(loss))(params, ids).jaxpr)
    fused = [splash_block_sizes(
        seq, cfg.attn_window if cfg.attn_kind(i) == "window" else 0
    ).use_fused_bwd_kernel for i in range(cfg.num_layers)]
    want = {"splash_mha_fwd_residuals": 3, "splash_mha_dkv_no_residuals": 3}
    if not all(fused):
        want["splash_mha_dq_no_residuals"] = fused.count(False)
    assert got == want


# L, heads, K/V heads, window: the tiles from before the sweep (a short
# sequence, a window's, a length nobody swept), then each swept length's
# fused backward: 512, 1024 (the train cells' sequence; and under a
# window not) and 1024 x 2048
_ARRANGEMENTS = [(256, 2, 2, 0), (256, 4, 2, 128), (2048, 4, 2, 0),
                 (1024, 6, 6, 0), (4096, 32, 8, 0), (4096, 4, 4, 128),
                 (8192, 4, 2, 0)]


@pytest.mark.skipif(jax.devices()[0].platform != "tpu",
                    reason="pallas TPU kernels")
@pytest.mark.parametrize("L,H,Hk,window", _ARRANGEMENTS)
def test_splash_matches_dense_on_tpu(L, H, Hk, window):
    """Forward AND gradients against ``dense_attention``, MHA and GQA,
    at a shape for each arrangement ``splash_block_sizes`` can return.
    The reference is dense attention in f32 at the highest matmul
    precision (one K/V head at a time: 32 heads of 4096 x 4096 f32
    logits and their gradients do not fit beside each other), so what
    is read is the kernels' own error."""
    rng = np.random.default_rng(2)
    q, k, v, do = (jnp.asarray(rng.normal(size=(1, L, h, 128)), jnp.bfloat16)
                   for h in (H, Hk, Hk, H))
    # a power of two: scaling q rounds nothing on either side
    kw = dict(causal=True, window=window, sm_scale=0.125)

    def grads(attend, q, k, v, do):
        out, vjp = jax.vjp(lambda *a: attend(*a, **kw), q, k, v)
        return (out, *vjp(do))

    def heads(x, g):                    # [1, L, Hk * g, D] -> [Hk, 1, L, g, D]
        return jnp.moveaxis(x.reshape(1, L, Hk, g, 128), 2, 0)

    @jax.jit
    def reference(q, k, v, do):
        with jax.default_matmul_precision("highest"):
            per_head = jax.lax.map(
                lambda a: grads(dense_attention, *a),
                tuple(heads(x.astype(jnp.float32), g)
                      for x, g in zip((q, k, v, do), (H // Hk, 1, 1, H // Hk))))
        return tuple(jnp.moveaxis(x, 0, 2).reshape(1, L, -1, 128)
                     for x in per_head)

    splash = jax.jit(functools.partial(grads, functools.partial(
        dot_product_attention, impl="splash")))(q, k, v, do)
    for name, s, d in zip(("out", "dq", "dk", "dv"), splash,
                          reference(q, k, v, do)):
        s, d = np.float32(s), np.float32(d)
        # the forward's tolerance; a gradient is a sum over up to L
        # terms, so it is held to it relative to its own scale
        scale = 1.0 if name == "out" else float(np.abs(d).max())
        np.testing.assert_allclose(s / scale, d / scale,
                                   atol=2e-2, rtol=2e-2, err_msg=name)
        # and as a whole: bf16 operands and one bf16 rounding of the
        # result read 0.21-0.26% of the reference's rms at the train
        # cells' shapes (the fused backward's dQ, a sum of bf16
        # partials, 0.26% for the two kernels' 0.245%: PERF.md section
        # 6, PR 35); a sum kept in bf16 would read several times that
        rms = float(np.sqrt(np.mean((s - d) ** 2)) / np.sqrt(np.mean(d ** 2)))
        assert rms < 5e-3, (name, rms)


def test_splash_runs_per_shard_on_a_mesh(monkeypatch):
    """On a mesh the Mosaic call sits under shard_map over the batch
    (dp x fsdp) and head (tp) axes: each device's kernel sees only its
    own rows and heads, and the pieces reassemble to dense attention.
    The kernel itself is TPU-only, so a dense per-example stand-in with
    the kernel's [H, L, D] contract records the shapes it was built for."""
    from edl_tpu.ops import attention
    from edl_tpu.parallel import MeshSpec, build_mesh

    built = []

    def fake_kernel(L, H):
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
