"""Mixture-of-experts MLP: routing invariants, grads, ep-mesh training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.ops.moe import MoEMLP, compute_routing


def _probs(B=2, S=8, E=4, seed=0):
    logits = np.random.default_rng(seed).normal(size=(B, S, E))
    return jax.nn.softmax(jnp.asarray(logits, jnp.float32), axis=-1)


def test_routing_no_drops_with_ample_capacity():
    probs = _probs()
    B, S, E = probs.shape
    K = 2
    dispatch, combine, aux, drops = compute_routing(probs, K, capacity=S * K)
    # every (token, k) slot placed exactly once
    assert float(dispatch.sum()) == B * S * K
    # each slot in a distinct (e, c) cell
    assert float(dispatch.max()) == 1.0
    # combine weights per token sum to 1 (top-k gates renormalised)
    np.testing.assert_allclose(np.asarray(combine.sum(axis=(2, 3))), 1.0,
                               rtol=1e-5)
    assert float(aux) > 0


def test_routing_drops_over_capacity():
    probs = _probs(S=16)
    dispatch, combine, _, _ = compute_routing(probs, 2, capacity=2)
    B, S, E = probs.shape
    assert float(dispatch.sum()) < B * S * 2       # overflow dropped
    assert float(dispatch.sum(axis=(1, 3)).max()) <= 2 * 1  # per-expert cap
    # dropped tokens lose combine mass but never exceed 1
    assert float(combine.sum(axis=(2, 3)).max()) <= 1.0 + 1e-5


def test_routing_position_bound():
    probs = _probs(B=1, S=32, E=2, seed=3)
    C = 5
    dispatch, _, _, drops = compute_routing(probs, 1, capacity=C)
    per_expert = dispatch.sum(axis=(0, 1))          # [E, C]
    assert per_expert.shape == (2, C)
    assert float(per_expert.max()) <= 1.0           # one token per cell


def test_routing_pad_tokens_claim_no_capacity():
    # serving prefill pads prompts to a bucket: with `valid`, the pad
    # positions must route nowhere, and the real tokens' routing must
    # be IDENTICAL to routing the unpadded prefix at the same capacity
    probs = _probs(B=1, S=12, E=4, seed=7)
    L, K, C = 8, 2, 3                      # tight capacity: drops happen
    valid = jnp.arange(12)[None, :] < L
    d_pad, c_pad, aux_pad, drops_pad = compute_routing(
        probs, K, capacity=C, valid=valid)
    d_ref, c_ref, aux_ref, drops_ref = compute_routing(
        probs[:, :L], K, capacity=C)
    assert float(d_pad[:, L:].sum()) == 0.0          # pads claim nothing
    assert float(c_pad[:, L:].sum()) == 0.0
    np.testing.assert_array_equal(np.asarray(d_pad[:, :L]),
                                  np.asarray(d_ref))
    np.testing.assert_allclose(np.asarray(c_pad[:, :L]), np.asarray(c_ref),
                               rtol=1e-6)
    assert int(drops_pad) == int(drops_ref)          # pads aren't "drops"
    np.testing.assert_allclose(float(aux_pad), float(aux_ref), rtol=1e-6)


def test_moe_mlp_forward_and_grad():
    model = MoEMLP(num_experts=4, mlp_dim=16, top_k=2,
                   dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 8, 12)),
                    jnp.float32)
    params = model.init(jax.random.key(0), x)["params"]
    y, aux = model.apply({"params": params}, x)
    assert y.shape == x.shape and np.isfinite(np.asarray(y)).all()
    assert np.isfinite(float(aux))

    def loss(p):
        y, aux = model.apply({"params": p}, x)
        return (y ** 2).mean() + 0.01 * aux

    g = jax.grad(loss)(params)
    for name in ("gate", "w_in", "w_out"):
        assert float(jnp.abs(g[name]).max()) > 0, f"no grad through {name}"


def test_single_expert_equals_plain_ffn():
    """E=1, K=1, ample capacity: MoE must reduce to silu FFN exactly."""
    model = MoEMLP(num_experts=1, mlp_dim=16, top_k=1,
                   capacity_factor=2.0, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 6, 8)),
                    jnp.float32)
    params = model.init(jax.random.key(1), x)["params"]
    y, _ = model.apply({"params": params}, x)
    w_in, w_out = params["w_in"][0], params["w_out"][0]
    want = jax.nn.silu(x @ w_in) @ w_out
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("ep", [1, 2])
def test_moe_transformer_trains_on_ep_mesh(ep):
    import optax

    from edl_tpu.models import TransformerConfig, TransformerLM
    from edl_tpu.models import transformer as tf_mod
    from edl_tpu.models.logical import logical_axes_from_paths
    from edl_tpu.models.transformer import lm_loss
    from edl_tpu.parallel import MeshSpec
    from edl_tpu.parallel.sharding import shard_host_batch
    from edl_tpu.train import ElasticTrainer, TrainConfig

    cfg = TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=16,
                            dtype=jnp.float32, attention_impl="dense",
                            remat=False, moe_experts=4, moe_top_k=2)
    model = TransformerLM(cfg)

    def loss_fn(params, extra, batch, rng):
        logits, aux = model.apply({"params": params}, batch["ids"][:, :-1],
                                  with_aux=True)
        return lm_loss(logits, batch["ids"][:, 1:]) + 0.01 * aux, (
            extra, {"moe_aux": aux})

    tr = ElasticTrainer(loss_fn, TrainConfig(
        mesh_spec=MeshSpec(dp=-1, ep=ep), log_every=0))

    def init():
        return model.init(jax.random.key(0),
                          jnp.zeros((1, 8), jnp.int32))["params"], None

    shape = jax.eval_shape(lambda: init()[0])
    logical = logical_axes_from_paths(shape, tf_mod.LOGICAL_RULES)
    # expert axes resolved onto ep
    assert logical["layers"]["moe"]["w_in"] == ("layers", "expert",
                                                "embed", "expert_mlp")
    state = tr.create_state(init, optax.adam(1e-2), param_logical=logical)
    ids = np.random.default_rng(0).integers(0, 64, (8, 17)).astype(np.int32)
    batch = shard_host_batch({"ids": ids}, tr.mesh, tr.rules)
    rng = jax.random.key(1)
    first = None
    for _ in range(10):
        state, metrics = tr.step_fn(state, batch, rng)
        first = float(metrics["loss"]) if first is None else first
    last = float(metrics["loss"])
    assert np.isfinite(last) and np.isfinite(float(metrics["moe_aux"]))
    assert last < first, f"loss did not drop: {first} -> {last}"


def test_routing_reports_drop_count():
    # 1 expert, capacity 2, 6 tokens top-1: 4 assignments must drop
    probs = jnp.asarray(np.full((1, 6, 1), 1.0, np.float32))
    _, _, _, drops = compute_routing(probs, 1, capacity=2)
    assert int(drops) == 4
    _, _, _, no_drops = compute_routing(probs, 1, capacity=6)
    assert int(no_drops) == 0


def _moe_cfg(capacity_factor):
    from edl_tpu.models import TransformerConfig
    return TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                             num_heads=4, mlp_dim=64, max_len=32,
                             dtype=jnp.float32, attention_impl="dense",
                             remat=False, moe_experts=4, moe_top_k=2,
                             moe_capacity=capacity_factor)


def test_generate_reports_prefill_drops():
    """Serving guardrail: an under-provisioned capacity_factor yields a
    NONZERO observable drop count at prefill; ample capacity reports 0
    (and decode steps never drop by construction)."""
    import jax as _jax

    from edl_tpu.models import TransformerLM
    from edl_tpu.models.generate import generate

    starving, ample = _moe_cfg(0.05), _moe_cfg(4.0)
    params = TransformerLM(starving).init(
        _jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    prompt = jnp.asarray(np.random.default_rng(1).integers(
        0, 64, (2, 16)), jnp.int32)

    _, drops = generate(starving, params, prompt, 4, temperature=0.0,
                        return_drops=True)
    assert int(drops) > 0, "starved capacity must report drops"
    toks, no_drops = generate(ample, params, prompt, 4, temperature=0.0,
                              return_drops=True)
    assert int(no_drops) == 0
    assert toks.shape == (2, 4)


def test_decode_gather_any_top_k():
    """The drop-free gather path gates on S alone: a single-token step
    with top_k > 8 must still use it (module promise), verified against
    the capacity path with ample capacity."""
    E, K, M = 12, 10, 16
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 1, M)),
                    jnp.float32)
    m = MoEMLP(num_experts=E, mlp_dim=32, top_k=K, capacity_factor=100.0,
               dtype=jnp.float32, decode=True)
    params = m.init(jax.random.key(0), x)
    y_gather, _ = m.apply(params, x)
    m2 = MoEMLP(num_experts=E, mlp_dim=32, top_k=K, capacity_factor=100.0,
                dtype=jnp.float32, decode=False)
    y_cap, _ = m2.apply(params, x)
    np.testing.assert_allclose(np.asarray(y_gather), np.asarray(y_cap),
                               atol=1e-5)


# -- the decode step's expert kernel (ops/moe.decode_gmm) -----------------

def _held(idx, E):
    """Expert indices of a router wider than the ``E`` experts held: the
    others become the sentinel ``E``, as ``MoEMLP.held`` routes them."""
    return np.minimum(idx, E)


def _spread(T, K, router, seed):
    rng = np.random.default_rng(seed)
    return np.stack([rng.permutation(router)[:K] for _ in range(T)])


# name: (M, H, E, dtype, gated, idx [T, K], valid [T] or None, bytes a
# weight chunk may take or None).  Widths are the three served
# configurations' cut to test size: granite-4.0-h-small 4096 x 768 with
# 36 of 72 held and top-10, OLMoE 2048 x 1024 with 64 and top-8,
# K-EXAONE 6144 x 2048 with 16 of 128 held, an expert streamed in
# several chunks of each projection.
GMM_CASES = {
    "granite_held_sentinel": (
        128, 24, 6, "bfloat16", True, _held(_spread(4, 10, 12, 0), 6), None,
        None),
    "olmoe_empty_experts": (
        64, 32, 8, "bfloat16", True, _spread(3, 2, 8, 1), None, None),
    "exaone_streamed_in_chunks": (
        384, 256, 4, "bfloat16", True, _held(_spread(12, 8, 32, 2), 4), None,
        128 * 384 * 2),
    "ungated": (
        64, 32, 8, "bfloat16", False, _spread(6, 2, 8, 3), None, None),
    "ungated_in_chunks": (
        256, 256, 4, "bfloat16", False, _spread(6, 2, 4, 4), None,
        128 * 256 * 2),
    "every_row_on_one_expert": (
        64, 32, 8, "bfloat16", True, np.full((5, 1), 3), None, None),
    "group_larger_than_the_widest_window": (
        64, 32, 4, "bfloat16", True,
        np.concatenate([np.full((150, 1), 2), _spread(9, 1, 4, 5)]), None,
        None),
    "groups_in_every_window_in_chunks": (
        256, 256, 4, "bfloat16", True,
        np.concatenate([np.full((7, 1), 0), np.full((25, 1), 1),
                        np.full((3, 1), 2), np.full((40, 1), 3)]), None,
        128 * 256 * 2),
    "valid_masks_pad_rows": (
        64, 32, 8, "bfloat16", True, _spread(12, 2, 8, 6),
        np.arange(12) % 3 != 1, None),
    "no_row_valid": (
        64, 32, 8, "bfloat16", True, _spread(4, 2, 8, 7), np.zeros(4, bool),
        None),
    "held_and_valid_float32": (
        64, 32, 5, "float32", True, _held(_spread(9, 4, 10, 8), 5),
        np.arange(9) % 4 != 0, None),
}


@pytest.mark.parametrize("case", list(GMM_CASES))
def test_decode_kernel_equals_the_ragged_dot_path(case, monkeypatch):
    """``dropless_experts`` with the decode kernel (interpret mode here)
    against the same call on ``ragged_dot``, its reference: the output
    within the operands' rounding (the kernel rounds the hidden rows
    once, the reference twice), the group sizes equal, and the expert
    weight sets the kernel counted itself fetching equal to the experts
    touched (none where nothing is touched)."""
    from edl_tpu.ops import moe

    M, H, E, dtype, gated, idx, valid, budget = GMM_CASES[case]
    if budget:
        monkeypatch.setattr(moe, "_CHUNK_BYTES", budget)
        tm, th = moe.gmm_chunks(M, H, gated, dtype)
        assert M // tm > 1 and H // th > 1
    else:
        assert moe.gmm_chunks(M, H, gated, dtype) == (M, H)
    T, K = idx.shape
    k = jax.random.split(jax.random.key(11), 5)
    x = jax.random.normal(k[0], (T, M), dtype)
    gates = jax.nn.softmax(jax.random.normal(k[1], (T, K)), axis=-1)
    w = [(jax.random.normal(kk, shape) * shape[1] ** -0.5).astype(dtype)
         for kk, shape in zip(k[2:], [(E, M, H), (E, M, H), (E, H, M)])]
    args = (x, gates, jnp.asarray(idx, jnp.int32), w[0] if gated else None,
            w[1], w[2], None if valid is None else jnp.asarray(valid))
    kw = dict(held_only=bool((idx >= E).any()))
    want, sizes, unread, _ = moe.dropless_experts(*args, **kw)
    got, sizes_k, fetched, took = moe.dropless_experts(*args, kernel=True,
                                                       **kw)
    assert unread is None and took is None
    assert got.dtype == want.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(sizes_k), np.asarray(sizes))
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    eps = float(jnp.finfo(dtype).eps)
    assert np.abs(got - want).max() <= 4 * eps * max(np.abs(want).max(), 1.0)
    if valid is not None:
        assert not got[~np.asarray(valid)].any()
    assert float(fetched) == int((np.asarray(sizes) > 0).sum())


def test_decode_kernel_dispatch_rule_is_shape_mesh_and_backend_only(
        monkeypatch):
    from edl_tpu.ops import moe

    bf16 = jnp.bfloat16
    assert not moe.applies(1, None, 320, 4096, bf16)    # this backend: no TPU
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    assert moe.applies(1, None, 320, 4096, bf16)
    assert not moe.applies(2, None, 320, 4096, bf16)    # verify pass, prefill
    assert not moe.applies(1, object(), 320, 4096, bf16)    # a mesh engine
    # the sorted rows live in VMEM: 2048 rows of 4096 do not fit there
    assert not moe.applies(1, None, 2048, 4096, bf16)
    # an expert streams in contiguous chunks of at most 4 MiB, whatever
    # the widths: rows of w_in and w_gate together, then rows of w_out
    assert moe.gmm_chunks(4096, 768, True, bf16) == (1024, 384)
    assert moe.gmm_chunks(2048, 1024, True, bf16) == (1024, 1024)
    assert moe.gmm_chunks(6144, 2048, True, bf16) == (512, 256)
    assert moe.gmm_chunks(6144, 2048, False, bf16) == (1024, 256)
    assert moe.gmm_chunks(6144, 2048, False, jnp.float32) == (512, 128)


# (pairs, width): the served decode-shaped calls, by the kernel's own op
# names in PERF_LEDGER.jsonl's breakdowns (rows x width as _gmm_rows pads
# them), and the block pass of 16 slots x 2 L = 8 positions x 8 experts
@pytest.mark.parametrize("pairs,width,rows,takes", [
    (96, 2048, 208, True), (96, 6144, 208, True), (320, 4096, 432, True),
    (256, 2304, 368, True), (96, 7680, 208, True),
    (512, 2048, 624, True),         # a pass of L rows a slot: 14.6 MiB
    (1024, 2048, 1136, True),       # a pass of 2 L rows a slot: 26.6 MiB
    (1104, 2048, 1216, False),      # 28.5 MiB: past the bound
    (512, 6144, 624, False)])
def test_decode_kernel_takes_the_rows_that_fit_its_vmem(monkeypatch, pairs,
                                                        width, rows, takes):
    """``_ROW_BYTES``: rows in and out, double buffered, and the float32
    accumulator, 12 B an element at bfloat16, beside 16 MiB of weight
    chunks and the float32 hidden rows in ``_VMEM_LIMIT``."""
    from edl_tpu.ops import moe

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    assert moe._gmm_rows(pairs, jnp.bfloat16) == rows
    assert moe._gmm_row_bytes(pairs, width, jnp.bfloat16) == rows * width * 12
    assert moe.applies(1, None, pairs, width, jnp.bfloat16) is takes
    assert moe._ROW_BYTES + 4 * moe._CHUNK_BYTES + moe._ROW_BYTES // 4 \
        < moe._VMEM_LIMIT


@pytest.mark.parametrize("held", [0, 4])
def test_moe_layer_takes_the_kernel_on_a_decode_shaped_call_only(
        held, monkeypatch):
    """``MoEMLP`` with ``decode`` and one token a slot runs the kernel
    where ``applies`` says so and sows what it fetched; a prefill-shaped
    call of the same layer, and the same call without ``decode``, stay
    on ``ragged_dot`` and sow nothing."""
    from edl_tpu.ops import moe

    monkeypatch.setattr(
        moe, "applies",
        lambda S, mesh, rows, M, dtype: S == 1 and mesh is None)
    kw = dict(num_experts=8, mlp_dim=32, top_k=2, capacity_factor=0.0,
              dtype=jnp.float32, gated=True, held=held)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(5, 1, 16)),
                    jnp.float32)
    mask = jnp.asarray([[True], [False], [True], [True], [False]])
    layer = MoEMLP(decode=True, **kw)
    params = layer.init(jax.random.key(0), x)["params"]
    (y, _), mut = layer.apply({"params": params}, x, mask,
                              mutable=["intermediates"])
    sown = mut["intermediates"]
    assert float(sown["moe_fetched"]) == float(sown["moe_stats"][1])
    calls = []
    monkeypatch.setattr(moe, "decode_gmm", lambda *a, **k: calls.append(a))
    (want, _), mut = MoEMLP(decode=False, **kw).apply(
        {"params": params}, x, mask, mutable=["intermediates"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    _, wide = layer.apply({"params": params}, x.reshape(1, 5, 16),
                          mask.reshape(1, 5), mutable=["intermediates"])
    assert not calls
    assert "moe_fetched" not in mut["intermediates"]
    assert "moe_fetched" not in wide["intermediates"]


# -- a multi-token call's live prefix (ops/moe.prefix_gmm) ----------------

def _front(T, K, router, held, live, seed):
    """``idx [T, K]`` of a ``router`` wide router of which exactly
    ``live`` pairs (None: as the draw falls) land on the ``held``
    experts this device has."""
    if live is None:
        return _spread(T, K, router, seed)
    rng = np.random.default_rng(seed)
    flat = rng.integers(held, router, size=T * K)
    at = rng.permutation(T * K)[:live]
    flat[at] = rng.integers(0, held, size=live)
    return flat.reshape(T, K)


# name: (M, H, router, held, K, T, dtype, real tokens or None, live pairs
# or None for the draw's, prefix).  The four held configurations' shares
# and widths cut to test size: openPangu 16 of 256 at 7680 x 2048,
# K-EXAONE 16 of 128 at 6144 x 2048, Kimi-Linear 64 of 256 at 2304 x
# 1024, granite 36 of 72 at 4096 x 768 top-10.
PREFIX_CASES = {
    "pangu_sixteenth": (256, 64, 64, 4, 8, 32, "bfloat16", None, None, 48),
    "exaone_eighth": (384, 128, 32, 4, 8, 16, "bfloat16", None, None, 32),
    "kimi_quarter": (128, 64, 32, 8, 8, 16, "bfloat16", None, None, 64),
    "granite_half_every_row": (
        128, 24, 12, 6, 10, 8, "bfloat16", None, None, 80),
    "padded_last_bucket": (
        256, 64, 64, 4, 8, 32, "bfloat16", 19, None, 48),
    "live_pairs_fill_the_prefix": (
        128, 32, 32, 4, 4, 24, "bfloat16", None, 32, 32),
    "one_pair_over_takes_the_whole_rows": (
        128, 32, 32, 4, 4, 24, "bfloat16", None, 33, 32),
    "no_live_pair": (128, 32, 32, 4, 4, 24, "bfloat16", None, 0, 32),
    "verify_pass_k_plus_1": (
        128, 32, 32, 4, 8, 3 * 5, "bfloat16", None, None, 3 * 5 * 8),
    "float32_ungated_prefix_off_the_tile": (
        64, 32, 16, 4, 2, 20, "float32", 17, None, 24),
}


@pytest.mark.parametrize("case", list(PREFIX_CASES))
def test_prefix_kernel_equals_the_ragged_dot_path(case):
    """``dropless_experts`` over the live prefix of the sorted pairs
    (``moe_prefix_gmm``, interpret mode here) against the same call on
    the whole rows and ``ragged_dot``: the output within the operands'
    rounding, the sizes equal, pad tokens zero; a call whose live pairs
    outgrow the prefix IS the whole-rows path, bit for bit, and says
    so."""
    from edl_tpu.ops import moe

    M, H, router, held, K, T, dtype, real, live, Rb = PREFIX_CASES[case]
    gated = "ungated" not in case
    idx = _front(T, K, router, held, live, 21)
    valid = None if real is None else jnp.arange(T) < real
    k = jax.random.split(jax.random.key(12), 5)
    x = jax.random.normal(k[0], (T, M), dtype)
    gates = jax.nn.softmax(jax.random.normal(k[1], (T, K)), axis=-1)
    w = [(jax.random.normal(kk, shape) * shape[1] ** -0.5).astype(dtype)
         for kk, shape in zip(k[2:], [(held, M, H), (held, M, H),
                                      (held, H, M)])]
    args = (x, gates, jnp.asarray(idx, jnp.int32), w[0] if gated else None,
            w[1], w[2], valid)
    want, sizes, *_ = moe.dropless_experts(*args, held_only=True)
    got, sizes_p, fetched, took = jax.jit(
        lambda *a: moe.dropless_experts(*a, held_only=True, prefix=Rb))(*args)
    assert fetched is None
    np.testing.assert_array_equal(np.asarray(sizes_p), np.asarray(sizes))
    pairs = int(np.asarray(sizes).sum())
    if live is not None:
        assert pairs == live
    assert got.dtype == want.dtype == x.dtype
    assert float(took) == (pairs <= Rb)
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    if pairs > Rb:
        np.testing.assert_array_equal(got, want)
    else:
        eps = float(jnp.finfo(dtype).eps)
        assert np.abs(got - want).max() <= 4 * eps * max(
            np.abs(want).max(), 1.0)
        assert pairs == 0 or np.abs(got).max() > 0
    if valid is not None:
        assert not got[~np.asarray(valid)].any()


def test_prefix_rule_is_shape_share_mesh_and_backend_only(monkeypatch):
    """``prefix_rows`` from what the call can observe: a bound for a
    chunk of the four held configurations (every row where they fit
    VMEM), None where what fits is under the margin over the expected
    live rows, where no share is held, with a mesh, outside ``decode``
    and off a TPU."""
    from edl_tpu.ops import moe

    bf16 = jnp.bfloat16
    pangu = (512, 8, 16, 256, 7680, 2048, bf16)
    assert moe.prefix_rows(*pangu) is None              # this backend: no TPU
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    Rb = moe.prefix_rows(*pangu)
    assert Rb % 16 == 0 and moe._PREFIX_MARGIN * 256 <= Rb < 4096
    # rows in and out once, float32 result and hidden rows, as padded
    assert (moe._gmm_rows(Rb, bf16) * (7680 * 8 + 2 * 2048 * 4)
            <= moe._PREFIX_ROW_BYTES
            < moe._gmm_rows(Rb + 16, bf16) * (7680 * 8 + 2 * 2048 * 4))
    exaone = moe.prefix_rows(256, 8, 16, 128, 6144, 2048, bf16)
    assert exaone % 16 == 0 and moe._PREFIX_MARGIN * 256 <= exaone < 2048
    assert moe.prefix_rows(256, 8, 64, 256, 2304, 1024, bf16) == 2048  # Kimi
    # granite: half of 2,560 rows live, 1.425 times that fit; of a chunk
    # twice as long the same rows are 0.71 times the live ones
    assert moe.prefix_rows(256, 10, 36, 72, 4096, 768, bf16) == 1824
    assert moe.prefix_rows(512, 10, 36, 72, 4096, 768, bf16) is None
    assert moe.prefix_rows(64, 10, 36, 72, 4096, 768, bf16) == 640
    assert moe.prefix_rows(64, 10, 60, 72, 4096, 768, bf16) is None
    assert moe.prefix_rows(5, 8, 16, 256, 7680, 2048, bf16) == 40  # k + 1
    assert moe.prefix_rows(512, 8, 0, 256, 7680, 2048, bf16) is None
    assert moe.prefix_rows(*pangu, mesh=object()) is None
    assert moe.prefix_rows(*pangu, decode=False) is None
    # an ungated layer has one hidden row a row: more rows fit
    assert moe.prefix_rows(*pangu, gated=False) > Rb


def test_held_layer_outside_decode_still_differentiates(monkeypatch):
    """Training differentiates the layer: without ``decode`` a held
    layer never takes the kernel, whatever the backend, and its
    gradients reach every weight; the same call under ``decode`` takes
    the prefix and sows that it did."""
    from edl_tpu.ops import moe

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    kw = dict(num_experts=16, mlp_dim=32, top_k=4, capacity_factor=0.0,
              dtype=jnp.float32, gated=True, held=4, router="sigmoid")
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 12, 16)),
                    jnp.float32)
    layer = MoEMLP(**kw)
    params = layer.init(jax.random.key(0), x)["params"]

    def loss(p):
        (y, aux), mut = layer.apply({"params": p}, x,
                                    mutable=["intermediates"])
        assert "moe_prefix" not in mut["intermediates"]
        return (y ** 2).mean() + 0.01 * aux

    g = jax.grad(loss)(params)
    for name in ("gate", "w_gate", "w_in", "w_out"):
        assert float(jnp.abs(g[name]).max()) > 0, f"no grad through {name}"
    (want, _), _ = layer.apply({"params": params}, x,
                               mutable=["intermediates"])
    (got, _), mut = MoEMLP(decode=True, **kw).apply(
        {"params": params}, x, mutable=["intermediates"])
    assert float(mut["intermediates"]["moe_prefix"]) == 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
