"""Kimi-Linear's block on the program's normal path against the plain
reference (``benchmarks/archs/kimi_linear.py``: float32, the delta rule
one token at a time, the latent attention un-absorbed, no chunking, no
kernels, no cache, no sort; nothing of ``edl_tpu`` in it), at a toy size
on the CPU: 8 layers in the published pattern (KDA with a dense MLP,
KDA, KDA, MLA, KDA, KDA, KDA, MLA: two whole periods), hidden 32, 2
heads, KDA keys and values of 16 with convolution 4 in chunks of 8, MLA
rank 24 / nope 16 / shared 8 / values 16, 8 sigmoid-routed experts top-3
of width 16 beside a shared one, ONE SHARE of four (this "device" holds
experts 0-1), vocabulary 64.  The system computes in float32 here so
that it routes as the reference does.

TOLERANCE: 1e-4 relative (of the largest reference magnitude), as
``test_granite_moe_hybrid.py`` has it and for its reasons.  Measured
here: 1e-7 to 5e-6.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models.transformer import (TransformerConfig, TransformerLM,
                                        lm_loss, param_count,
                                        train_bytes_estimate)
from edl_tpu.ops.moe import MoEMLP
from edl_tpu.serving.engine import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 1e-4
LAYERS, ROUTER, HELD, TOP_K, CHUNK, BLOCK = 8, 8, 2, 3, 8, 8
CONF = {"model_type": "kimi_linear", "hidden_act": "silu", "hidden_size": 32,
        "intermediate_size": 64, "kv_lora_rank": 24, "head_dim": 16,
        "linear_attn_config": {
            "full_attn_layers": [4, 8], "head_dim": 16,
            "kda_layers": [1, 2, 3, 5, 6, 7], "num_heads": 2,
            "short_conv_kernel_size": 4},
        "mla_use_nope": True, "moe_intermediate_size": 16,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 2,
        "num_expert_group": 1, "num_experts": HELD, "router_experts": ROUTER,
        "num_experts_per_token": TOP_K, "num_hidden_layers": LAYERS,
        "num_key_value_heads": 2, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5, "rope_scaling": None,
        "rope_theta": 10000, "routed_scaling_factor": 2.446,
        "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_topk": True, "v_head_dim": 16, "vocab_size": 64,
        "first_k_dense_replace": 1,
        "run": {"compute_dtype": "float32", "param_dtype": "float32",
                "kda_chunk": CHUNK, "prefill_chunk": 16,
                "absorbed_prefix": 112}}
KDA, MLA, SPARSE = 6, 2, 7


def bench_arch():
    path = os.path.join(ROOT, "benchmarks", "archs", "kimi_linear.py")
    spec = importlib.util.spec_from_file_location("bench_kimi", path)
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
    by themselves, norm scales, the selection bias and the convolutions
    moved off their defaults) with the embedding's rows small, so that
    the best logit is the layers' doing."""
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

@pytest.mark.parametrize("length", [5, 16, 21, 24])
def test_full_forward_equals_the_reference(params, length):
    """Lengths that are (16, 24) and are not (5, 21) multiples of the
    delta rule's chunk."""
    ids = ids_of(length, batch=2)
    close(TransformerLM(CFG).apply({"params": params}, ids),
          ref.logits(CONF, params, ids))


def test_the_counts_agree(params):
    """The program's count, the benchmark's own from the published keys,
    and the tree; and the real configuration file's."""
    n = sum(a.size for a in jax.tree.leaves(params))
    assert param_count(CFG) == n == ref.param_count(CONF)
    assert train_bytes_estimate(CFG, 2, 32) > 16 * n
    path = os.path.join(ROOT, "benchmarks", "configs",
                        "kimi-linear-48b-a3b-serve-ep4.json")
    with open(path) as f:
        conf = json.load(f)
    real = ref.transformer_config(conf, max_len=conf["run"]["max_len"])
    assert (param_count(real) == ref.param_count(conf)
            == conf["memory"]["parameters"])
    assert real.layer_attn == ("kda", "kda", "kda", "latent") * 2
    assert real.layer_mlp == ("dense",) + ("sparse",) * 7
    # a slot: one 1,280-byte row (576 values in whole lane tiles) a
    # token a latent layer; 2 MiB of float32 + 72 KB a KDA layer
    assert real.mla_width == 576 and real.mla_row == 640
    assert ref.state_bytes_per_slot(conf) == 6 * ((2 << 20) + 3 * 12288 * 2)


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


def tiles_of_128(monkeypatch, max_len):
    """The expanded path's tile at its smallest, 128 rows, so that a toy
    slab of ``max_len`` is several tiles long."""
    from edl_tpu.ops import latent_attention
    monkeypatch.setattr(latent_attention, "_TILE_BYTES", 1)
    assert 128 < max_len and max_len % 128 == 0


@pytest.mark.parametrize("path,max_len,calls", [
    ("einsum", 128, (16, 13) + (1,) * 8),
    ("kernel", 128, (16, 13) + (1,) * 8),
    # a slab of four tiles of 128 rows: a chunk inside the first tile, one
    # that crosses its edge, one that ends AT the next edge, a remainder
    ("einsum", 512, (104, 56, 96, 13) + (1,) * 3),
])
def test_prefill_in_chunks_then_decode_through_the_cache(
        params, path, max_len, calls, monkeypatch):
    """A 16-token chunk, a 13-token chunk (a remainder of the delta
    rule's chunk) with state and latent rows carried, then 8 one-token
    steps: every call's logits equal the reference's one full pass.
    ``kernel``: the one-token steps through ``kda_step``,
    ``latent_append`` and ``latent_attend`` in interpret mode."""
    if path == "kernel":
        from edl_tpu.ops import decode_attention, ssm
        monkeypatch.setattr(ssm, "applies",
                            lambda L, mesh: L == 1 and mesh is None)
        monkeypatch.setattr(decode_attention, "applies",
                            lambda L, mesh, T: L == 1 and mesh is None)
    if max_len > 128:
        tiles_of_128(monkeypatch, max_len)
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


def test_the_mixers_alone_and_the_state_equal_the_reference(params):
    got = ref.block_agreement(CONF, params, ids_of(45, seed=5),
                              ref.reference(CONF, params, ids_of(45, seed=5)),
                              cfg=CFG)
    for key in ("mixer_error", "attention_error", "absorbed_error",
                "expert_error", "routed_error", "state_error"):
        assert np.max(got[key]) <= RTOL, (key, np.max(got[key]))
    assert np.max(got["logit_error_sigma"]) <= RTOL
    assert np.max(got["cache_error_sigma"]) <= RTOL
    assert got["expert_sets_differ"] == 0.0
    assert got["absorbed_error"].size == MLA * ref.CACHE_STEPS
    assert got["state_error"].size == KDA * 1     # a tenth of two heads: one


@pytest.mark.parametrize("kept", ["float32", "bfloat16"])
def test_the_state_a_mixers_cache_carries_is_the_recurrences(params, kept):
    """What the benchmark holds ``run.kda_state_dtype`` by: in float32
    the reference recurrence's final state; kept in bfloat16, rounded at
    every update, far from it."""
    p = params["layer_1"]["kda"]
    y = jax.random.normal(jax.random.key(41), (1, 29, 32))
    with jax.default_matmul_precision("highest"):
        want = ref.kda_mixer(CONF, p, y)[1][0]
    got = ref.program_state(dataclasses.replace(
        CFG, kda_state_dtype=jnp.dtype(kept)), p, y, CHUNK)
    err = ref._rel(got - want, want, axes=(-2, -1))
    assert (err.max() <= RTOL) == (kept == "float32"), err


def test_loss_and_gradients_of_the_training_forward(params):
    """The unrolled training forward (the chunked delta rule and the
    expanded attention under ``jax.grad``) against the reference's."""
    ids = ids_of(25, seed=9, batch=2)
    inputs, targets = ids[:, :-1], ids[:, 1:]
    model = TransformerLM(dataclasses.replace(CFG, remat=True))

    def loss(p):
        return lm_loss(model.apply({"params": p}, inputs), targets)

    def ref_loss(p):
        return lm_loss(ref.logits(CONF, p, inputs), targets)

    (lt, gt), (lr, gr) = (jax.value_and_grad(f)(params)
                          for f in (loss, ref_loss))
    close(lt, lr, 1e-5)
    flat_t = jax.tree_util.tree_leaves_with_path(gt)
    flat_r = dict(jax.tree_util.tree_leaves_with_path(gr))
    assert len(flat_t) == len(flat_r)
    scale = max(float(jnp.abs(g).max()) for g in flat_r.values())
    for path, g in flat_t:
        err = float(jnp.abs(g - flat_r[path]).max()) / scale
        assert err <= 1e-3, (jax.tree_util.keystr(path), err)


# -- the engine ------------------------------------------------------------------

def test_a_pooled_answer_equals_the_cold_answer_equals_the_reference(params):
    """41 tokens: two chunks and a remainder on the chunk lane; the
    second time from the pool's latent blocks and the state snapshot at
    the prompt's deepest block edge (40)."""
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
    assert s1["kv_state_snapshots"] >= 1


@pytest.mark.parametrize("max_len", [64, 96, 4096])
def test_what_a_slot_holds(params, max_len):
    """The latent is cached once: one row a token a latent layer (32
    values here, in one 128-lane tile), no head axis; a KDA layer's
    state whatever ``max_len`` is."""
    eng = engine(params, max_len=max_len, kv_pool_blocks=8)
    try:
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["kv_slot_bytes_latent"] == MLA * max_len * 128 * 4
    assert stats["kv_slot_bytes_state"] == KDA * (
        2 * 16 * 16 * 4 + 3 * 3 * 32 * 4)
    assert stats["kv_slot_bytes_global"] == stats["kv_slot_bytes_window"] == 0


@pytest.mark.parametrize("path", ["einsum", "kernel"])
def test_the_counters_are_the_hosts_recount(params, path, monkeypatch):
    """One request alone: 10-token prompt in a 16 bucket, 9 tokens out =
    the prefill's and 8 steps = 2 programs of 4.  The state counters are
    the ones every state layer shares; the latent ones count positions:
    needed = the slot's length at each step, fetched = every slot's slab
    on the einsum path, whole tiles of the live slot under the kernel's
    plan."""
    if path == "kernel":
        from edl_tpu.ops import decode_attention, ssm
        monkeypatch.setattr(ssm, "applies",
                            lambda L, mesh: L == 1 and mesh is None)
        monkeypatch.setattr(decode_attention, "applies",
                            lambda L, mesh, T: L == 1 and mesh is None)
    eng = engine(params, max_len=128)
    try:
        served(eng, np.asarray(ids_of(10, seed=14))[0].tolist(), 9)
        s = eng.stats()
    finally:
        eng.stop()
    assert s["ssm_state_steps"] == 1 * 8 * KDA
    assert s["ssm_state_steps_run"] == (3 if path == "einsum" else 1
                                        ) * 8 * KDA
    assert s["ssm_prefill_positions"] == 16
    assert s["ssm_prefill_positions_pad"] == 6
    assert s["latent_tokens_live"] == MLA * sum(range(11, 19))
    assert s["latent_tokens_read"] == MLA * 8 * (
        3 * 128 if path == "einsum" else 128)
    # the prefill's expanded path: a 16-token call from row 0, one tile
    assert s["latent_prefill_rows_live"] == MLA * 16
    assert s["latent_prefill_rows_read"] == MLA * 128
    assert s["moe_assignments_routed"] == TOP_K * SPARSE * s["moe_tokens"]
    assert s["moe_tokens"] == 10 + 8
    assert s["moe_prefill_drops"] == 0


def test_the_prefill_rows_are_the_hosts_recount(params, monkeypatch):
    """A 300-token prompt through the chunk lane in chunks of 64 against
    a slab of four tiles of 128 rows, then the same prompt again, which
    hits the pool up to its snapshot at row 296: a call, lane and latent
    layer, live = the call's end (offset + length, a last bucket's pads
    counted), read = whole tiles up to it."""
    tiles_of_128(monkeypatch, 512)
    eng = engine(params, max_len=512, prefill_chunk=64, kv_pool_blocks=128,
                 prefill_buckets=(8, 16, 32, 64))
    prompt = np.asarray(ids_of(300, seed=15))[0].tolist()
    try:
        served(eng, prompt, 2)
        cold = eng.stats()
        served(eng, prompt, 2)
        hit = eng.stats()
    finally:
        eng.stop()
    ends = [64, 128, 192, 256, 256 + 64]
    assert cold["prefill_chunks"] == len(ends)
    assert cold["latent_prefill_rows_live"] == MLA * sum(ends)
    assert cold["latent_prefill_rows_read"] == MLA * (128 + 128 + 256 + 256
                                                      + 384)
    # the suffix of 4 tokens in a bucket of 8 from row 296: three tiles
    assert hit["kv_prefix_hits"] - cold["kv_prefix_hits"] == 1
    assert hit["kv_prefill_tokens_skipped"] == 296
    assert (hit["latent_prefill_rows_live"]
            - cold["latent_prefill_rows_live"]) == MLA * (296 + 8)
    assert (hit["latent_prefill_rows_read"]
            - cold["latent_prefill_rows_read"]) == MLA * 384


def test_what_the_chunk_program_holds(params, monkeypatch):
    """The chunk program against a slab of 32 tiles: no float32 array of
    ``[.., chunk, max_len]`` is left in it (the scores are a tile's), and
    the latent layers' loops over tiles have no constant trip count (it
    follows the cache index), where the delta rule's scans over the
    chunk's blocks have one."""
    import re
    T, C = 4096, 16
    tiles_of_128(monkeypatch, T)
    eng = engine(params, max_len=T, prefill_chunk=C, kv_pool_blocks=0,
                 kv_block=0)
    try:
        slab, drops = eng._chunk_start()
        compiled = eng._chunk_mid_fn(C).lower(
            eng._params, slab, jnp.zeros((1, C), jnp.int32), drops).compile()
    finally:
        eng.stop()
    text = compiled.as_text()
    assert not re.search(rf"f32\[[0-9,]*\b{C},{T}\]", text)
    assert re.search(rf"f32\[[0-9,]*\b{C},128\]", text)      # a tile's
    whiles = [ln for ln in text.splitlines() if re.search(r"= .* while\(", ln)]
    latent = [ln for ln in whiles if "attn/latent" in ln]
    assert len(latent) == MLA, whiles
    assert not any("known_trip_count" in ln for ln in latent)
    assert any("known_trip_count" in ln for ln in whiles)     # the scans
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 4 * 2 * C * T          # under ONE head's whole-slab scores


@pytest.mark.parametrize("stack", ["kda+latent", "ssm+global"])
def test_the_fit_prices_a_tile_only_where_the_attention_is_latent(
        params, stack, monkeypatch):
    """``_require_fit`` (through a device that reports a limit) asks the
    expanded path for its tile, once a rung of the ladder it tries, for
    this stack; a stack with a GQA layer among its state-space layers
    (Granite's kinds) keeps the whole-slab price and never asks."""
    from edl_tpu.ops import latent_attention

    class _Chip:
        device_kind = "toy chip"

        def memory_stats(self):
            return {"bytes_limit": 1 << 40, "bytes_in_use": 0}

    cfg = CFG
    if stack == "ssm+global":
        cfg = TransformerConfig(
            vocab_size=64, num_layers=2, embed_dim=32, num_heads=2,
            mlp_dim=64, max_len=96, dtype=jnp.float32, remat=False,
            attention_impl="dense", layer_attn=("ssm", "global"),
            ssm_heads=2, ssm_head_dim=16, ssm_state=8, ssm_groups=1,
            ssm_conv=4, ssm_chunk=8)
        params = TransformerLM(cfg).init(
            jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    asked = []
    tile = latent_attention.expand_block
    monkeypatch.setattr(latent_attention, "expand_block",
                        lambda *a: asked.append(a) or tile(*a))
    eng = engine(params, cfg=cfg, max_len=512)
    try:
        monkeypatch.setattr(jax, "devices", lambda: [_Chip()])
        asked.clear()                 # the constructor's shape traces
        eng._require_fit(3, BLOCK, 48, 4)
        rungs = eng.PREFILL_KS
    finally:
        eng.stop()
    # the widest rung fits a device this large: one price asked, its own
    want = [(rungs[0], 16, 2, 32, 512, jnp.float32, False)]
    assert asked == (want if stack == "kda+latent" else [])


def test_session_export_and_import_carry_latent_blocks_and_the_snapshot(
        params):
    prompt = np.asarray(ids_of(27, seed=13))[0].tolist()
    a = engine(params)
    answer = served(a, prompt, 6, session="s")
    assert a.drain(60)
    exported = a.export_sessions()
    assert len(exported) == 1
    session, tokens, meta, blob = exported[0]
    # down to the deepest node that owns a snapshot: the prompt's edge
    assert len(tokens) == 24
    assert meta["latent_layers"] == ["layer_3", "layer_7"]
    assert len(meta["state_layers"]) == KDA
    # 3 blocks of 8 rows of 128 float32 a latent layer, one state a KDA
    # layer (S and the convolution's tail)
    assert len(blob) == MLA * 3 * 8 * 128 * 4 + KDA * (
        2 * 16 * 16 * 4 + 3 * 3 * 32 * 4)
    b = engine(params)
    try:
        assert b.import_session(session, tokens, meta, blob) == 3
        nxt = prompt + answer + [9, 8, 7]
        s0 = b.stats()
        out = served(b, nxt, 5, session="s")
        s1 = b.stats()
        assert out == greedy(params, nxt, 5)
        assert (s1["kv_prefill_tokens_skipped"]
                - s0["kv_prefill_tokens_skipped"]) == 24
    finally:
        b.stop()


@pytest.mark.parametrize("what", ["spec_k", "mesh", "latent_spec_k",
                                  "latent_mesh"])
def test_what_cannot_serve_the_stack_refuses_at_construction(params, what):
    cfg = CFG
    if what.startswith("latent"):
        # a latent layer alone refuses too, for its own reasons
        cfg = dataclasses.replace(CFG, num_layers=1, layer_attn=("latent",),
                                  layer_mlp=("dense",))
    if what.endswith("spec_k"):
        kw = dict(spec_k=2, draft_cfg=cfg, draft_params=params)
        reason = ("writes at one" if what.startswith("latent")
                  else "cannot be rewound")
    else:
        from jax.sharding import Mesh
        kw = dict(mesh=Mesh(np.asarray(jax.devices()[:2]), ("tp",)))
        reason = ("no head axis" if what.startswith("latent")
                  else "no sharding yet")
    with pytest.raises(ValueError, match=reason):
        ContinuousBatcher(cfg, params, slots=2, max_len=64, temperature=0.0,
                          **kw)


@pytest.mark.parametrize("field,value", [
    ("kda_heads", 0), ("kda_head_dim", 0), ("kda_conv", 0), ("kda_chunk", 0),
    ("mla_rank", 0), ("mla_nope_dim", 0), ("mla_v_dim", 0),
    ("mla_rope_dim", 0), ("mla_rope_dim", 7)])
def test_an_incomplete_plan_is_refused(field, value):
    with pytest.raises(ValueError, match="needs"):
        dataclasses.replace(CFG, **{field: value})


def test_a_configuration_without_the_new_kinds_is_what_it_was():
    """The new fields left off: the old kinds' modules, parameters and
    cache are untouched by them."""
    plain = TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                              num_heads=2, mlp_dim=64, max_len=32,
                              dtype=jnp.float32)
    odd = dataclasses.replace(plain, kda_heads=5, kda_chunk=3, mla_rank=9,
                              mla_rope=True)
    a, b = (jax.eval_shape(lambda c=c: TransformerLM(c).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))) for c in (plain, odd))
    assert jax.tree.structure(a) == jax.tree.structure(b)
    assert param_count(plain) == param_count(odd)


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
    uncut = dict(CONF, num_experts=ROUTER)
    with jax.default_matmul_precision("highest"):
        want, _, routed = ref.moe_mlp(uncut, whole, y)
    shared = want - routed

    def share(lo):
        return dict(whole, **{n: whole[n][lo:lo + HELD]
                              for n in ("w_gate", "w_in", "w_out")})

    with jax.default_matmul_precision("highest"):
        parts = [ref.held_experts(CONF, share(lo), y, (lo, lo + HELD))[0]
                 for lo in range(0, ROUTER, HELD)]
    close(sum(parts) + shared, want)

    # the program's layer holds experts 0..held-1: give each share's
    # experts that place by rolling the router's columns
    def layer(shared_dim):
        return MoEMLP(num_experts=ROUTER, mlp_dim=16, top_k=TOP_K,
                      capacity_factor=0.0, dtype=jnp.float32, gated=True,
                      norm_topk=True, router="sigmoid", select_bias=True,
                      routed_scale=2.446, shared_dim=shared_dim, held=HELD)

    got = []
    for lo in range(0, ROUTER, HELD):
        p = share(lo)
        p["gate"] = jnp.roll(whole["gate"], -lo, axis=1)
        p["gate_bias"] = jnp.roll(whole["gate_bias"], -lo)
        p = {k: v for k, v in p.items() if not k.startswith("shared")}
        (out, _), _ = layer(0).apply({"params": p}, y[None],
                                     mutable=["intermediates"])
        got.append(out[0])
    for mine, theirs in zip(got, parts):
        close(mine, theirs)
    (full, _), _ = layer(16).apply({"params": share(0)}, y[None],
                                   mutable=["intermediates"])
    close(full[0] + sum(got[1:]), want)
