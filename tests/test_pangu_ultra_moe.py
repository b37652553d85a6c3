"""openPangu-Ultra-MoE's block on the program's normal path against the
plain reference (``benchmarks/archs/pangu_ultra_moe.py``: float32, the
latent attention un-absorbed with a low-rank query and rotated shared key
dims, sandwich norms, no cache, no kernels, no chunks, no sort; nothing
of ``edl_tpu`` in it), at a toy size on the CPU: 3 layers, EVERY one
latent (the first with a dense MLP, then two sparse), hidden 32, 4 heads,
query rank 12, latent rank 24 / nope 16 / rotated shared 8 / values 16,
8 sigmoid-routed experts top-3 of width 16 beside a shared one, ONE SHARE
of four (this "device" holds experts 0-1), vocabulary 64.  The system
computes in float32 here so that it routes as the reference does.

TOLERANCE: 1e-4 relative (of the largest reference magnitude), as
``test_kimi_linear.py`` has it and for its reasons.  Measured here: 1e-7
to 4e-6.  Each deliberately wrong program reads 1e-2 or more.
"""

import dataclasses
import importlib.util
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models.transformer import (TransformerConfig, TransformerLM,
                                        param_count)
from edl_tpu.ops.moe import MoEMLP
from edl_tpu.serving.engine import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 1e-4
LAYERS, ROUTER, HELD, TOP_K, BLOCK = 3, 8, 2, 3, 8
CONF = {"model_type": "pangu_ultra_moe", "hidden_act": "silu",
        "attention_bias": False, "hidden_size": 32, "intermediate_size": 64,
        "kv_lora_rank": 24, "q_lora_rank": 12,
        "max_position_embeddings": 131072, "moe_intermediate_size": 16,
        "n_routed_experts": HELD, "router_experts": ROUTER,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_experts_per_tok": TOP_K,
        "num_hidden_layers": LAYERS, "num_key_value_heads": 4,
        "num_nextn_predict_layers": 0, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5, "rope_theta": 25600000,
        "routed_scaling_factor": 2.5, "sandwich_norm": True,
        "tie_word_embeddings": False, "v_head_dim": 16, "vocab_size": 64,
        "first_k_dense_replace": 1,
        "run": {"compute_dtype": "float32", "param_dtype": "float32",
                "prefill_chunk": 16, "absorbed_prefix": 112,
                "absorbed_start": 16384}}
SPARSE = 2
REAL = "openpangu-ultra-moe-718b-serve-ep16"


def bench_arch():
    path = os.path.join(ROOT, "benchmarks", "archs", "pangu_ultra_moe.py")
    spec = importlib.util.spec_from_file_location("bench_pangu", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


ref = bench_arch()
CFG = ref.transformer_config(CONF, max_len=96, remat=False,
                             attention_impl="dense")


def error(got, want):
    want = np.asarray(want)
    return np.abs(np.asarray(got) - want).max() / np.abs(want).max()


def close(got, want, rtol=RTOL):
    assert np.shape(got) == np.shape(want)
    err = error(got, want)
    assert err <= rtol, f"relative error {err:.2e} over {rtol:.0e}"


def ids_of(length, seed=3, batch=1):
    return jax.random.randint(jax.random.key(seed), (batch, length), 1, 64)


@pytest.fixture(scope="module")
def params():
    """The benchmark's own seeded weights (expert matrices lecun-normal
    by themselves, norm scales off 1) with the embedding's rows small,
    so that the best logit is the layers' doing."""
    p = ref.init_params(CFG, 7, "float32")
    p["tok_embed"]["embedding"] = p["tok_embed"]["embedding"] * 0.1
    return p


def engine(params, cfg=CFG, **kw):
    kw = dict(dict(slots=3, max_len=96, temperature=0.0, steps_per_sync=4,
                   kv_block=BLOCK, kv_pool_blocks=48, prefill_chunk=16,
                   prefill_buckets=(8, 16, 32)), **kw)
    return ContinuousBatcher(cfg, params, **kw)


def greedy(params, prompt, n):
    """The reference's own continuation, one full pass a token."""
    ids = list(prompt)
    for _ in range(n):
        ids.append(int(ref.logits(CONF, params, jnp.asarray([ids]))[0, -1]
                       .argmax()))
    return ids[len(prompt):]


def served(eng, prompt, n, **kw):
    return eng.submit(np.asarray(prompt, np.int32), n, **kw).result(
        300).tolist()


# -- the block -----------------------------------------------------------------

@pytest.mark.parametrize("length", [5, 24])
def test_full_forward_equals_the_reference(params, length):
    ids = ids_of(length, batch=2)
    close(TransformerLM(CFG).apply({"params": params}, ids),
          ref.logits(CONF, params, ids))


def test_the_routers_do_not_follow_the_seed(params):
    """``init_params`` draws the routers from a fixed key: another seed
    moves every other leaf and no ``gate``."""
    other = ref.init_params(CFG, 8, "float32")
    for i in range(1, LAYERS):
        a, b = params[f"layer_{i}"], other[f"layer_{i}"]
        assert np.array_equal(a["moe"]["gate"], b["moe"]["gate"])
        assert not np.array_equal(a["moe"]["w_in"], b["moe"]["w_in"])
        assert not np.array_equal(a["mla"]["q_a"]["kernel"],
                                  b["mla"]["q_a"]["kernel"])
    assert not np.array_equal(params["layer_1"]["moe"]["gate"],
                              params["layer_2"]["moe"]["gate"])


def real_conf():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{REAL}.json")) as f:
        return json.load(f)


def test_the_counts_agree(params):
    """The program's count, the benchmark's own from the published keys,
    and the tree; and the real configuration file's
    ``memory.parameters``."""
    n = sum(a.size for a in jax.tree.leaves(params))
    assert param_count(CFG) == n == ref.param_count(CONF)
    conf = real_conf()
    real = ref.transformer_config(conf, max_len=conf["run"]["max_len"])
    assert (param_count(real) == ref.param_count(conf)
            == conf["memory"]["parameters"] == 4_919_139_840)
    assert ref.mla_matmul_params(conf) + 512 + 1536 == 196_577_280
    assert ref.expert_params(conf) == 47_185_920
    assert real.layer_attn == ("latent",) * 5
    assert real.layer_mlp == ("dense",) + ("sparse",) * 4
    assert (real.num_heads, real.mla_q_rank, real.mla_rank, real.mla_rope,
            real.post_norms) == (128, 1536, 512, True, True)
    assert (real.moe_experts, real.moe_held, real.moe_top_k,
            real.moe_routed_scale, real.moe_select_bias) == (
                256, 16, 8, 2.5, False)
    # a slot: one 1,280-byte row (576 values in whole lane tiles) a
    # token a layer, five layers: 6,400 B a token
    assert real.mla_width == 576 and real.mla_row == 640
    assert ref.kv_bytes_per_token(conf) == 5 * 1152
    # at 128 heads a latent row is at this chip's ridge (197e12 / 819e9)
    flops, nbytes = ref.latent_attention_min(conf, 1e6, 0.0)
    assert 240 < flops / nbytes < 244


def test_the_file_keeps_every_published_number():
    """The catalog's copy of config.json, where this sandbox has it:
    every number under the same key, but for the five in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "openPangu-Ultra-MoE-718B")
    conf = real_conf()
    assert conf["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if conf.get(k) != v}
    assert differ == set(conf["reduced"]) == set(conf["reduced_from"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert all(conf["reduced_from"][k] == entry["config"][k] for k in differ)
    assert conf["router_experts"] == entry["config"]["n_routed_experts"]


def test_an_unmapped_key_is_refused():
    for key, value in (("sliding_window", 128), ("rope_scaling", {"a": 1}),
                       ("topk_method", "noaux_tc"), ("n_group", 8)):
        with pytest.raises(ValueError, match=key):
            ref.transformer_config(dict(CONF, **{key: value}), max_len=64)
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        ref.transformer_config(dict(CONF, num_nextn_predict_layers=1),
                               max_len=64)
    with pytest.raises(ValueError, match="q_lora_rank"):
        ref.transformer_config(dict(CONF, q_lora_rank=None), max_len=64)
    with pytest.raises(ValueError, match="router_experts"):
        ref.transformer_config(dict(CONF, n_routed_experts=9), max_len=64)


def decode_model(max_len=64, cfg=CFG):
    return TransformerLM(dataclasses.replace(cfg, decode=True,
                                             max_len=max_len))


def fresh_cache(model, batch):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init(jax.random.key(0),
                               jnp.zeros((batch, 1), jnp.int32),
                               positions=jnp.zeros((batch, 1), jnp.int32))
        )["cache"])


@pytest.mark.parametrize("path,max_len,calls", [
    ("einsum", 128, (16, 13) + (1,) * 8),
    ("kernel", 128, (16, 13) + (1,) * 8),
    # a slab of four tiles of 128 rows: a chunk inside the first tile, one
    # that crosses its edge, one that ends AT the next edge, a remainder
    ("einsum", 512, (104, 56, 96, 13) + (1,) * 3),
])
def test_prefill_in_chunks_then_decode_through_the_cache(
        params, path, max_len, calls, monkeypatch):
    """Chunks with the ROTATED latent rows carried, then one-token steps
    on the absorbed path (the query rotated at its own position): every
    call's logits equal the reference's one full pass.  ``kernel``: the
    one-token steps through ``latent_append`` and ``latent_attend`` in
    interpret mode."""
    from edl_tpu.ops import decode_attention, latent_attention
    if path == "kernel":
        monkeypatch.setattr(decode_attention, "applies",
                            lambda L, mesh, T: L == 1 and mesh is None)
    if max_len > 128:
        monkeypatch.setattr(latent_attention, "_TILE_BYTES", 1)
    model = decode_model(max_len)
    ids = ids_of(sum(calls))
    want = ref.logits(CONF, params, ids)
    cache, at = fresh_cache(model, 1), 0
    for n in calls:
        logits, mut = model.apply(
            {"params": params, "cache": cache}, ids[:, at:at + n],
            positions=at + jnp.arange(n)[None],
            mutable=["cache", "intermediates"])
        close(logits, want[:, at:at + n])
        cache, at = mut["cache"], at + n


def test_the_parts_alone_equal_the_reference(params):
    got = ref.block_agreement(CONF, params, ids_of(45, seed=5),
                              ref.reference(CONF, params, ids_of(45, seed=5)),
                              cfg=CFG)
    for key in ("attention_error", "absorbed_error", "expert_error",
                "routed_error"):
        assert np.max(got[key]) <= RTOL, (key, np.max(got[key]))
    assert np.max(got["logit_error_sigma"]) <= RTOL
    assert np.max(got["cache_error_sigma"]) <= RTOL
    assert got["expert_sets_differ"] == 0.0
    assert got["attention_error"].size == LAYERS * 45
    assert got["absorbed_error"].size == LAYERS * ref.CACHE_STEPS


class _NoQueryNorm(nn.Module):
    """``transformer.RMSNorm`` with the one named ``q_norm`` the
    identity (its scale still declared): the low-rank query's norm left
    out of the program."""
    dtype: object = jnp.float32
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        if self.name == "q_norm":
            return x.astype(self.dtype)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(self.dtype) * scale


def _no_query_norm(cfg, monkeypatch):
    from edl_tpu.models import transformer
    monkeypatch.setattr(transformer, "RMSNorm", _NoQueryNorm)
    return cfg


WRONG = {
    # the low-rank query's RMSNorm left out
    "query_norm": _no_query_norm,
    # the post-norms left out: plain pre-norm residuals
    "post_norm": lambda cfg, _: dataclasses.replace(cfg, post_norms=False),
    # q_pe and k_pe not rotated
    "rotation_off": lambda cfg, _: dataclasses.replace(cfg, mla_rope=False),
    # the gates' factor 2.5 left out
    "no_scale": lambda cfg, _: dataclasses.replace(cfg, moe_routed_scale=1.0),
    # the chosen scores not divided by their sum
    "no_renorm": lambda cfg, _: dataclasses.replace(cfg, moe_norm_topk=False),
}


@pytest.mark.parametrize("what", sorted(WRONG))
def test_a_wrong_program_is_far_from_the_reference(params, what,
                                                   monkeypatch):
    """Each variant a later edit could make by accident fails the full
    forward's tolerance by two orders of magnitude."""
    cfg = WRONG[what](CFG, monkeypatch)
    ids = ids_of(24, batch=2)
    err = error(TransformerLM(cfg).apply({"params": params}, ids),
                ref.logits(CONF, params, ids))
    assert err > 100 * RTOL, (what, err)


# -- the engine ------------------------------------------------------------------

def test_a_pooled_answer_equals_the_cold_answer_equals_the_reference(params):
    """41 tokens: two chunks and a remainder on the chunk lane; the
    second time from the pool's latent blocks (rotated rows) up to the
    prompt's deepest block edge (40): every layer's cache is of the one
    latent class, no state snapshot is taken."""
    prompt = np.asarray(ids_of(41, seed=12))[0].tolist()
    eng = engine(params)
    try:
        cold = served(eng, prompt, 9)
        s0 = eng.stats()
        pooled = served(eng, prompt, 9)
        s1 = eng.stats()
    finally:
        eng.stop()
    assert cold == pooled == greedy(params, prompt, 9)
    assert s1["kv_prefix_hits"] - s0["kv_prefix_hits"] == 1
    assert (s1["kv_prefill_tokens_skipped"]
            - s0["kv_prefill_tokens_skipped"]) == 40
    assert s1["kv_state_snapshots"] == s1["kv_window_snapshots"] == 0


def test_the_counters_are_the_hosts_recount(params):
    """One request alone: 10-token prompt in a 16 bucket, 9 tokens out =
    the prefill's and 8 steps = 2 programs of 4; then a 41-token prompt
    through the chunk lane (16, 16, a last bucket of 16 holding 9)."""
    eng = engine(params, max_len=128)
    try:
        served(eng, np.asarray(ids_of(10, seed=14))[0].tolist(), 9)
        s = eng.stats()
        served(eng, np.asarray(ids_of(41, seed=15))[0].tolist(), 1)
        t = eng.stats()
    finally:
        eng.stop()
    assert s["latent_tokens_live"] == LAYERS * sum(range(11, 19))
    assert s["latent_decode_calls"] == LAYERS * 8
    # the prompt's 10 real queries: row i sees i + 1 rows
    assert s["latent_prefill_pairs"] == LAYERS * 55
    assert s["moe_assignments_routed"] == TOP_K * SPARSE * s["moe_tokens"]
    assert s["moe_tokens"] == 10 + 8 and s["moe_prefill_drops"] == 0
    assert s["ssm_state_steps"] == s["ssm_prefill_positions"] == 0
    assert t["latent_decode_calls"] == s["latent_decode_calls"]
    assert (t["latent_prefill_pairs"] - s["latent_prefill_pairs"]
            == LAYERS * 41 * 42 // 2)


def test_a_chunked_prompt_counts_the_prefix_kernels_calls(params,
                                                          monkeypatch):
    """A 41-token prompt is three multi-token programs (chunks of 16 and
    16, a last bucket of 16 holding 9): where ``ops/moe.prefix_rows``
    gives their expert layers a bound (forced here: this backend is no
    TPU, the kernel runs in interpret mode) every sparse layer call
    takes the prefix kernel and the engine says so; on the CPU's own
    path none does.  The pairs counted and the tokens served are the
    same either way."""
    from edl_tpu.ops import moe

    prompt = np.asarray(ids_of(41, seed=15))[0].tolist()
    got = {}
    for forced in (False, True):
        if forced:
            monkeypatch.setattr(moe, "_on_tpu", lambda: True)
        eng = engine(params, max_len=128)
        try:
            got[forced] = served(eng, prompt, 5), eng.stats()
        finally:
            eng.stop()
    (toks, s), (toks_k, k) = got[False], got[True]
    assert toks == toks_k == greedy(params, prompt, 5)
    assert s["moe_prefill_groups"] == k["moe_prefill_groups"] == SPARSE * 3
    assert s["moe_prefix_kernel_calls"] == 0
    assert k["moe_prefix_kernel_calls"] == SPARSE * 3
    for name in ("moe_assignments", "moe_assignments_routed", "moe_tokens",
                 "moe_prefill_drops", "moe_prefill_experts_touched",
                 "moe_decode_layer_steps", "moe_decode_experts_touched"):
        assert s[name] == k[name], name
    assert s["moe_prefill_drops"] == 0 < s["moe_assignments"]


def test_an_all_latent_stack_builds_fits_and_warms(params, monkeypatch):
    """A stack with ONLY the latent class: the slot is rows alone,
    ``_require_fit`` (through a device that reports a limit) counts one
    row a token a layer a slot, and ``warm()`` compiles no
    state-snapshot program."""
    class _Chip:
        device_kind = "toy chip"

        def memory_stats(self):
            return {"bytes_limit": 1 << 40, "bytes_in_use": 0}

    eng = engine(params, max_len=128, kv_pool_blocks=64)
    try:
        stats = eng.stats()
        assert stats["kv_slot_bytes_latent"] == LAYERS * 128 * 128 * 4
        assert (stats["kv_slot_bytes_state"] == stats["kv_slot_bytes_global"]
                == stats["kv_slot_bytes_window"] == 0)
        assert eng._kv.n_snaps == 0 and not eng._snapped
        monkeypatch.setattr(jax, "devices", lambda: [_Chip()])
        assert eng._require_fit(3, BLOCK, 64, 0) == 0
        monkeypatch.undo()
        eng.warm(24)      # a chunk of 16 and a last bucket
        kinds = {k[0] for k in eng._prefill_cache if isinstance(k[0], str)}
        assert "load" not in kinds and "reuse" in kinds and "chunk" in kinds
    finally:
        eng.stop()
    # at the published widths: 640 values of 2 bytes a token a layer
    conf = real_conf()
    real = ref.transformer_config(conf, max_len=conf["run"]["max_len"])
    assert real.mla_row * 2 * real.num_layers == 6400


@pytest.mark.parametrize("what", ["spec_k", "mesh"])
def test_what_cannot_serve_the_stack_refuses_at_construction(params, what):
    if what == "spec_k":
        kw = dict(spec_k=2, draft_cfg=CFG, draft_params=params)
        reason = "writes at one"
    else:
        from jax.sharding import Mesh
        kw = dict(mesh=Mesh(np.asarray(jax.devices()[:2]), ("tp",)))
        reason = "no head axis"
    with pytest.raises(ValueError, match=reason):
        ContinuousBatcher(CFG, params, slots=2, max_len=64, temperature=0.0,
                          **kw)


def test_a_configuration_without_the_new_fields_is_what_it_was():
    """The new fields left off: a latent stack's modules and parameters
    are the ones it had (``q_proj``, two norms a block)."""
    plain = dataclasses.replace(CFG, mla_q_rank=0, post_norms=False)
    tree = jax.eval_shape(lambda: TransformerLM(plain).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    assert set(tree["layer_1"]) == {"attn_norm", "mla", "mlp_norm", "moe"}
    assert set(tree["layer_1"]["mla"]) == {"q_proj", "kv_a", "kv_norm",
                                           "kv_b", "o_proj"}
    dense = TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                              num_heads=2, mlp_dim=64, max_len=32,
                              dtype=jnp.float32)
    odd = dataclasses.replace(dense, mla_q_rank=7)
    a, b = (jax.eval_shape(lambda c=c: TransformerLM(c).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))) for c in (dense,
                                                                     odd))
    assert jax.tree.structure(a) == jax.tree.structure(b)
    assert param_count(dense) == param_count(odd)


# -- the four shares -------------------------------------------------------------

def test_the_four_shares_sum_to_the_uncut_layer(params):
    """Expert parallelism without its exchange: shares 0-3 (experts 0-1,
    2-3, 4-5, 6-7), each with the router whole and the gates normalised
    over all the chosen, add up, the shared expert counted once, to the
    uncut reference's layer; in the reference and in the program alike."""
    key = jax.random.key(21)
    held = params["layer_1"]["moe"]
    whole = dict(held)
    for name in ("w_gate", "w_in", "w_out"):
        key, k = jax.random.split(key)
        whole[name] = jax.random.normal(
            k, (ROUTER,) + held[name].shape[1:]) * held[name].shape[1] ** -0.5
    y = jax.random.normal(jax.random.key(22), (11, 32))
    uncut = dict(CONF, n_routed_experts=ROUTER)
    want, _, routed = ref.moe_mlp(uncut, whole, y)
    shared = want - routed

    def share(lo):
        return dict(whole, **{n: whole[n][lo:lo + HELD]
                              for n in ("w_gate", "w_in", "w_out")})

    parts = [ref.held_experts(CONF, share(lo), y, (lo, lo + HELD))[0]
             for lo in range(0, ROUTER, HELD)]
    close(sum(parts) + shared, want)

    # the program's layer holds experts 0..held-1: give each share's
    # experts that place by rolling the router's columns
    def layer(shared_dim):
        return MoEMLP(num_experts=ROUTER, mlp_dim=16, top_k=TOP_K,
                      capacity_factor=0.0, dtype=jnp.float32, gated=True,
                      norm_topk=True, router="sigmoid", select_bias=False,
                      routed_scale=2.5, shared_dim=shared_dim, held=HELD)

    got = []
    for lo in range(0, ROUTER, HELD):
        p = share(lo)
        p["gate"] = jnp.roll(whole["gate"], -lo, axis=1)
        p = {k: v for k, v in p.items() if not k.startswith("shared")}
        (out, _), _ = layer(0).apply({"params": p}, y[None],
                                     mutable=["intermediates"])
        got.append(out[0])
    for mine, theirs in zip(got, parts):
        close(mine, theirs)
    (full, _), _ = layer(16).apply({"params": share(0)}, y[None],
                                   mutable=["intermediates"])
    close(full[0] + sum(got[1:]), want)
