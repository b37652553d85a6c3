"""OLMoE on the program's normal path against the plain reference
(``tests/helpers/olmoe_reference.py``: the published layer in float32,
no kernels, no cache, no sort), at a small size on the CPU: 2 layers,
hidden 64, 4 heads, 8 gated experts of width 32, top-2 without
renormalising, QK-norm, epsilon 1e-5, vocab 128.  The system computes
in float32 here so that it routes exactly as the reference does.

TOLERANCE: 1e-4 relative (of the largest reference magnitude).  Both
sides are float32, but not the same sums: the program sorts rows by
expert and multiplies each group, the reference applies every expert to
every token and adds 8 weighted terms of which 6 are exact zeros; XLA's
CPU matmuls accumulate in another order than "highest" asks of the
reference.  Measured here: 5e-8 to 3e-7 (float32 rounding; on another
CPU's matmul kernels it may be several times that).  Anything structural (a
renormalised gate, a missing q_norm, epsilon 1e-6 against non-unit
scales, a dropped or misrouted token, an ungated expert) is 1e-2 or
more.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models.generate import generate
from edl_tpu.models.transformer import (TransformerConfig, TransformerLM,
                                        active_matmul_params, param_count)
from edl_tpu.ops.moe import MoEMLP
from edl_tpu.serving.engine import ContinuousBatcher
from tests.helpers import olmoe_reference as ref

RTOL = 1e-4

CONF = {"hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
        "num_hidden_layers": 2, "vocab_size": 128, "rms_norm_eps": 1e-5,
        "rope_theta": 10000, "norm_topk_prob": False,
        "tie_word_embeddings": False}
CFG = TransformerConfig(
    vocab_size=128, num_layers=2, embed_dim=64, num_heads=4, num_kv_heads=4,
    mlp_dim=32, max_len=96, rope_theta=10000.0, dtype=jnp.float32,
    remat=False, attention_impl="dense", norm_eps=1e-5, qk_norm=True,
    moe_experts=8, moe_top_k=2, moe_capacity=0.0, moe_gated=True,
    moe_norm_topk=False)


def close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err <= rtol, f"relative error {err:.2e} over {rtol:.0e}"


@pytest.fixture(scope="module")
def params():
    """Seeded weights; every norm scale is moved off 1 so that a
    missing norm, a misplaced scale or another epsilon shows."""
    p = TransformerLM(CFG).init(jax.random.key(0),
                                jnp.zeros((1, 4), jnp.int32))["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(p)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    return treedef.unflatten([
        a * (1.0 + 0.3 * jax.random.normal(k, a.shape))
        if path[-1].key == "scale" else a
        for (path, a), k in zip(leaves, keys)])


def ids_of(n, seed=0, batch=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, 128, (batch, n)), jnp.int32)


def test_full_forward_logits_against_the_reference(params):
    ids = ids_of(24, batch=2)
    close(TransformerLM(CFG).apply({"params": params}, ids),
          ref.logits(CONF, params, ids))


@pytest.mark.parametrize("wrong", [
    {"moe_norm_topk": True}, {"qk_norm": False}, {"norm_eps": 1e-2},
    {"moe_gated": False}])
def test_the_tolerance_catches_a_structural_difference(params, wrong):
    ids = ids_of(24, batch=2)
    got = TransformerLM(dataclasses.replace(CFG, **wrong)).apply(
        {"params": params}, ids)
    want = np.asarray(ref.logits(CONF, params, ids))
    assert np.abs(np.asarray(got) - want).max() / np.abs(want).max() > 1e-2


def test_prefill_then_decode_through_the_cache_logits(params):
    """The path ``generate`` runs (decode-mode model, one cache):
    prefill 13 tokens, then 9 single-token steps teacher-forced, two
    rows; every position's logits against the reference's full forward
    pass."""
    from edl_tpu.models.generate import _split_layer_params
    ids = ids_of(22, seed=3, batch=2)
    P = 13
    model = TransformerLM(dataclasses.replace(CFG, decode=True))
    split = _split_layer_params(params, CFG.num_layers)
    cache = model.init(jax.random.key(0), ids[:, :1],
                       positions=jnp.zeros((2, 1), jnp.int32))["cache"]
    cache = jax.tree.map(jnp.zeros_like, cache)
    out, mut = model.apply(
        {"params": split, "cache": cache}, ids[:, :P],
        positions=jnp.broadcast_to(jnp.arange(P), (2, P)), mutable=["cache"])
    rows = [out]
    for t in range(P, 22):
        step, mut = model.apply(
            {"params": split, "cache": mut["cache"]}, ids[:, t:t + 1],
            positions=jnp.full((2, 1), t, jnp.int32), mutable=["cache"])
        rows.append(step)
    close(jnp.concatenate(rows, axis=1), ref.logits(CONF, params, ids))


def shortfall(params, prompt, answer):
    """How far the reference's logit of each served token lies under
    the reference's best, over the best's magnitude (teacher-forced on
    the served answer: logits, not tokens)."""
    seq = jnp.asarray([list(prompt) + list(answer)], jnp.int32)
    at = np.asarray(ref.logits(CONF, params, seq[:, :-1]))[0][
        len(prompt) - 1:]
    served = at[np.arange(len(answer)), np.asarray(answer)]
    return float(((at.max(-1) - served) / np.abs(at).max(-1)).max())


def test_generate_against_the_reference(params):
    prompt = np.asarray(ids_of(11, seed=5))[0]
    out = np.asarray(generate(CFG, params, jnp.asarray(prompt[None]), 8,
                              temperature=0.0))[0]
    assert shortfall(params, prompt, out) <= RTOL


def test_engine_bucketed_and_chunked_prefill_against_the_reference(params):
    """Two slots at different positions: a 40-token prompt through the
    chunked prefill (chunk 16: two mid chunks and a padded final one)
    and a 5-token prompt through a padded bucket, decoding together."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in (40, 5)]
    eng = ContinuousBatcher(CFG, params, slots=2, temperature=0.0, top_k=0,
                            steps_per_sync=2, kv_block=4, kv_pool_blocks=49,
                            prefill_chunk=16)
    try:
        futs = [eng.submit(p, 10) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["chunked_admissions"] == 1 and stats["prefill_chunks"] == 3
    for p, out in zip(prompts, outs):
        assert len(out) == 10
        assert shortfall(params, p, out) <= RTOL
    assert stats["moe_prefill_drops"] == 0
    L, K = CFG.num_layers, CFG.moe_top_k
    assert stats["moe_assignments"] >= L * K * (45 + 2 * 9)
    # the host's own count of what it routed: the identity the
    # benchmark's `correct` rests on (runners/serve_arch.py)
    assert stats["moe_assignments"] == L * K * stats["moe_tokens"]
    # 3 chunk programs + 1 bucketed prefill, each L layer calls
    assert stats["moe_prefill_groups"] == 4 * L
    assert stats["moe_prefill_max_load_sum"] >= stats["moe_prefill_groups"]


def test_engine_counts_what_the_expert_layers_did(params):
    """One request, one token step a sync: the counters are exact."""
    prompt = np.asarray(ids_of(9, seed=9))[0]
    eng = ContinuousBatcher(CFG, params, slots=3, temperature=0.0, top_k=0,
                            steps_per_sync=1, kv_block=0, prefill_chunk=0)
    try:
        eng.generate(prompt, 6, timeout=300)
        stats = eng.stats()
    finally:
        eng.stop()
    L, K = CFG.num_layers, CFG.moe_top_k
    # 9 prompt tokens (the bucket's pads route nowhere), then the 5
    # tokens fed back; the two free slots are masked out of the routing
    assert stats["moe_assignments"] == L * K * (9 + 5)
    assert stats["moe_tokens"] == 9 + 5
    assert stats["moe_decode_layer_steps"] == L * 5
    # one live token a step touches exactly its top-k experts
    assert stats["moe_decode_experts_touched"] == K * L * 5
    assert stats["moe_prefill_groups"] == L
    assert 1 <= stats["moe_prefill_experts_touched"] <= L * CFG.moe_experts
    assert stats["moe_prefill_drops"] == 0


def test_engine_serves_the_same_tokens_through_the_decode_kernel(
        params, monkeypatch):
    """The decode step's expert FFN as ``ops/moe.decode_gmm`` (interpret
    mode here; ``applies`` answered as a TPU without a mesh would)
    against the same engine on ``ragged_dot``: the same greedy tokens,
    and the step programs' own count of the expert weight sets the
    kernel fetched equals the experts the batches touched.  Off the
    kernel the counter stays 0: ``ragged_dot``'s reads are not the
    program's to count."""
    from edl_tpu.ops import moe

    prompts = [np.asarray(ids_of(n, seed=n))[0] for n in (9, 5, 12)]

    def served():
        eng = ContinuousBatcher(CFG, params, slots=4, temperature=0.0,
                                top_k=0, steps_per_sync=2, kv_block=0,
                                prefill_chunk=0)
        try:
            futs = [eng.submit(p, 7) for p in prompts]
            return [f.result(timeout=300) for f in futs], eng.stats()
        finally:
            eng.stop()

    want, plain = served()
    monkeypatch.setattr(
        moe, "applies",
        lambda S, mesh, rows, M, dtype: S == 1 and mesh is None)
    got, stats = served()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert plain["moe_decode_experts_fetched"] == 0
    assert stats["moe_decode_experts_touched"] > 0
    assert (stats["moe_decode_experts_fetched"]
            == stats["moe_decode_experts_touched"])
    assert (stats["moe_decode_layer_steps"]
            == plain["moe_decode_layer_steps"])


def test_a_capacity_path_engine_breaks_the_routing_identity(params):
    """The same model through the capacity path (what a dropped token
    looks like to the counters): the host still counts the tokens it
    routed, the expert layers report no assignments for them."""
    prompt = np.asarray(ids_of(9, seed=9))[0]
    eng = ContinuousBatcher(dataclasses.replace(CFG, moe_capacity=1.0),
                            params, slots=3, temperature=0.0, top_k=0,
                            steps_per_sync=1, kv_block=0, prefill_chunk=0)
    try:
        eng.generate(prompt, 2, timeout=300)
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["moe_tokens"] >= 9
    assert stats["moe_assignments"] != (
        CFG.num_layers * CFG.moe_top_k * stats["moe_tokens"])


def test_no_dispatch_tensor_and_no_weight_gather_in_the_engines_programs(
        params):
    """What the decode step and one prefill trace to (the jaxpr: on the
    CPU ``ragged_dot`` is expanded when it is LOWERED, on the TPU it
    becomes a Mosaic kernel): grouped matmuls, no ``[B, S, E, C]``
    dispatch / combine tensor and no ``[B, S, K, M, H]`` gather of
    expert weights.  The same patterns do find both in a capacity-path
    engine's programs."""
    import re

    def programs(cfg):
        eng = ContinuousBatcher(cfg, params, slots=3, temperature=0.0, top_k=0,
                                steps_per_sync=2, kv_block=0,
                                prefill_chunk=0)
        try:
            key = jax.random.key(0)
            step = eng._step_jit.trace(
                eng._cache, jnp.asarray(eng._toks), key, eng._params,
                eng._live_mask([0])).jaxpr
            prefill = eng._prefill_fn(32, 2).trace(
                eng._params, jnp.zeros((2, 32), jnp.int32),
                jnp.ones((2,), jnp.int32), key).jaxpr
        finally:
            eng.stop()
        return str(step), str(prefill)

    # [.., K=2, M=64, H=32] or [.., K, H, M]: a per-token weight gather;
    # [B, S, E=8, C]: the capacity path's one-hot routing tensors
    gather = re.compile(r"\[(\d+,)+2,(64,32|32,64)\]")
    dispatch = re.compile(r"\[\d+,\d+,8,\d+\]")
    for text in programs(CFG):
        assert text.count("ragged_dot") >= 3 * CFG.num_layers
        assert not gather.search(text) and not dispatch.search(text)
    step, prefill = programs(dataclasses.replace(CFG, moe_capacity=1.25))
    assert gather.search(step) and dispatch.search(prefill)
    assert "ragged_dot" not in step + prefill


def moe_layer(**kw):
    return MoEMLP(num_experts=8, mlp_dim=32, top_k=2,
                  dtype=jnp.float32, gated=True,
                  **{"capacity_factor": 0.0, "norm_topk": False, **kw})


@pytest.fixture(scope="module")
def layer():
    x = jax.random.normal(jax.random.key(2), (2, 24, 64))
    return moe_layer().init(jax.random.key(3), x)["params"], x


def test_expert_layer_loss_and_gradients_against_the_reference(layer):
    p, x = layer
    target = jax.random.normal(jax.random.key(4), x.shape)

    def loss_sys(p, x):
        y, _ = moe_layer().apply({"params": p}, x)
        return jnp.mean(jnp.square(y - target))

    def loss_ref(p, x):
        y = ref.moe_mlp(CONF, p, x.reshape(-1, 64)).reshape(x.shape)
        return jnp.mean(jnp.square(y - target))

    (l_s, g_s) = jax.value_and_grad(loss_sys, argnums=(0, 1))(p, x)
    (l_r, g_r) = jax.value_and_grad(loss_ref, argnums=(0, 1))(p, x)
    close(l_s, l_r)
    for got, want in zip(jax.tree.leaves(g_s), jax.tree.leaves(g_r)):
        close(got, want)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(g_s))


def test_a_padded_prompt_routes_exactly_as_the_unpadded_one(layer):
    p, x = layer
    n = 15
    mask = jnp.broadcast_to(jnp.arange(24)[None, :] < n, (2, 24))
    (y_pad, _), m_pad = moe_layer().apply({"params": p}, x, mask,
                                          mutable=["intermediates"])
    (y_cut, _), m_cut = moe_layer().apply({"params": p}, x[:, :n],
                                          mutable=["intermediates"])
    np.testing.assert_allclose(y_pad[:, :n], y_cut, rtol=0, atol=1e-6)
    assert float(jnp.abs(y_pad[:, n:]).max()) == 0.0   # pads get nothing
    np.testing.assert_array_equal(m_pad["intermediates"]["moe_stats"],
                                  m_cut["intermediates"]["moe_stats"])
    assert m_cut["intermediates"]["moe_stats"][0] == 2 * n * 2


def test_norm_topk_on_and_off_differ_as_they_should(layer):
    p, x = layer
    y_off, _ = moe_layer().apply({"params": p}, x)
    y_on, _ = moe_layer(norm_topk=True).apply({"params": p}, x)
    close(y_on, ref.moe_mlp(dict(CONF, norm_topk_prob=True), p,
                            x.reshape(-1, 64)).reshape(x.shape))
    # renormalising divides a token's output by the sum of its top-k
    # router probabilities, and by nothing else
    probs = jax.nn.softmax(x @ p["gate"], -1)
    kept = jax.lax.top_k(probs, 2)[0].sum(-1, keepdims=True)
    close(y_off, y_on * kept)
    assert float(jnp.abs(y_on - y_off).max()) > 0.1 * float(
        jnp.abs(y_on).max())


def test_dropless_drops_nothing_where_capacity_1x_does(layer):
    p, x = layer
    (y_cap, _), m_cap = moe_layer(capacity_factor=1.0).apply(
        {"params": p}, x, mutable=["intermediates"])
    (y, _), m = moe_layer().apply({"params": p}, x,
                                  mutable=["intermediates"])
    drops = int(m_cap["intermediates"]["moe_drops"])
    assert drops > 0                       # capacity 1 x overflows here
    assert "moe_drops" not in m["intermediates"]
    stats = np.asarray(m["intermediates"]["moe_stats"])
    assert stats[0] == x.shape[0] * x.shape[1] * 2      # every pair routed
    assert 1 <= stats[1] <= 8 and stats[2] >= 1.0
    want = ref.moe_mlp(CONF, p, x.reshape(-1, 64)).reshape(x.shape)
    close(y, want)
    assert np.abs(np.asarray(y_cap) - np.asarray(want)).max() > 1e-2


def test_capacity_path_with_gated_experts_and_its_decode_gather(layer):
    """The capacity path keeps its decode gather; with ample capacity
    both agree with the reference for gated experts too."""
    p, x = layer
    want = ref.moe_mlp(CONF, p, x.reshape(-1, 64)).reshape(x.shape)
    y, _ = moe_layer(capacity_factor=8.0).apply({"params": p}, x)
    close(y, want)
    y1, _ = moe_layer(capacity_factor=8.0, decode=True).apply(
        {"params": p}, x[:, :1])
    close(y1, want[:, :1])


def test_the_two_copies_of_the_reference_are_equal(params):
    """``benchmarks/archs/olmoe.py`` carries the benchmark's copy."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "archs", "olmoe.py")
    spec = importlib.util.spec_from_file_location("bench_olmoe", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    ids = ids_of(17, seed=11, batch=2)
    h_bench, chosen = bench.forward(CONF, params, ids)[:2]
    h_test, chosen_test = ref.forward(CONF, params, ids)
    np.testing.assert_array_equal(np.asarray(h_bench), np.asarray(h_test))
    np.testing.assert_array_equal(np.asarray(chosen),
                                  np.asarray(chosen_test))
    assert chosen.shape == (2, 2, 17, 2)


def test_param_count_and_flops_of_gated_experts():
    from edl_tpu.obs.flops import (analytic_lm_flops_per_token,
                                   config_flops_per_token)
    p = TransformerLM(CFG).init(jax.random.key(0),
                                jnp.zeros((1, 4), jnp.int32))["params"]
    assert param_count(CFG) == sum(a.size for a in jax.tree.leaves(p))
    ungated = dataclasses.replace(CFG, moe_gated=False, qk_norm=False)
    assert param_count(CFG) - param_count(ungated) == 2 * (
        8 * 64 * 32 + 2 * 64)
    # OLMoE-1B-7B as published: 6.9 B parameters, 1.3 B active a token
    olmoe = dataclasses.replace(
        CFG, vocab_size=50304, num_layers=16, embed_dim=2048, num_heads=16,
        num_kv_heads=16, mlp_dim=1024, moe_experts=64, moe_top_k=8)
    assert param_count(olmoe) == 6_919_161_856
    active = active_matmul_params(olmoe)
    assert active == 16 * (4 * 2048 ** 2 + 2048 * 64
                           + 8 * 3 * 2048 * 1024) + 2048 * 50304
    assert config_flops_per_token(olmoe, 4096) == 6 * active + (
        6 * 16 * 4096 * 2048)
    dense = TransformerConfig(num_layers=3, embed_dim=256, num_heads=2,
                              mlp_dim=512, vocab_size=1000)
    assert config_flops_per_token(dense, 128) == \
        analytic_lm_flops_per_token(3, 256, 512, 1000, 128)
