"""K-EXAONE's block on the program's normal path against the plain
reference (``tests/helpers/exaone_moe_reference.py``: float32, no
kernels, no cache, no ring, no sort), at a toy size on the CPU: 8
layers in the published pattern (three window layers then a global one,
twice; layer 0 dense, the rest sparse), hidden 48, 4 query heads over 2
KV heads of head size 16 (not 48 / 4), window 8, a sigmoid router over
16 experts with a selection bias and a factor 2.5, top-4, a shared
expert, and ONE SHARE of four: this "device" holds experts 0-3.  The
system computes in float32 here so that it routes as the reference does.

TOLERANCE: 1e-4 relative (of the largest reference magnitude), as
``test_olmoe.py`` has it and for its reasons: both sides are float32
but not the same sums (the program sorts rows by expert, the reference
applies every held expert to every token; a ring and a slab hold the
same keys in another order; XLA's CPU matmuls accumulate in another
order than "highest").  Measured here: 1e-6 to 3e-6.  Every structural
variant below is 2e-3 or more.
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
                                        param_count)
from edl_tpu.ops import moe as moe_ops
from edl_tpu.ops.moe import MoEMLP
from edl_tpu.serving.engine import ContinuousBatcher
from tests.helpers import exaone_moe_reference as ref

RTOL = 1e-4
LAYERS, WINDOW, ROUTER, HELD, TOP_K = 8, 8, 16, 4, 4
TYPES = ["sliding_attention"] * 3 + ["full_attention"]
CONF = {"hidden_size": 48, "intermediate_size": 96,
        "moe_intermediate_size": 24, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_experts": HELD,
        "router_experts": ROUTER, "num_experts_per_tok": TOP_K,
        "num_hidden_layers": LAYERS, "vocab_size": 128,
        "rms_norm_eps": 1e-5, "rope_parameters": {"rope_theta": 1e6},
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "sliding_window": WINDOW, "layer_types": TYPES * 2,
        "mlp_layer_types": ["dense"] + ["sparse"] * 7}
CFG = TransformerConfig(
    vocab_size=128, num_layers=LAYERS, embed_dim=48, num_heads=4,
    num_kv_heads=2, attn_head_dim=16, mlp_dim=96, moe_mlp_dim=24, max_len=96,
    rope_theta=1e6, dtype=jnp.float32, remat=False, attention_impl="dense",
    norm_eps=1e-5, qk_norm=True, qk_norm_per_head=True, attn_window=WINDOW,
    layer_attn=tuple("window" if t == "sliding_attention" else "global"
                     for t in CONF["layer_types"]),
    layer_mlp=tuple(CONF["mlp_layer_types"]), rope_global=False,
    moe_experts=ROUTER, moe_held=HELD, moe_top_k=TOP_K, moe_capacity=0.0,
    moe_gated=True, moe_norm_topk=True, moe_router="sigmoid",
    moe_select_bias=True, moe_routed_scale=2.5, moe_shared_dim=24)
SPARSE = LAYERS - 1


def close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err <= rtol, f"relative error {err:.2e} over {rtol:.0e}"


def error(got, want):
    want = np.asarray(want)
    return np.abs(np.asarray(got) - want).max() / np.abs(want).max()


def seeded(tree, key=1):
    """Weights a comparison can see through: norm scales moved off 1,
    a selection bias that is not zero, each expert matrix lecun-normal
    by itself (``MoEMLP``'s initialiser counts the expert axis as a
    receptive field: PERF.md section 6, PR 26)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    keys = jax.random.split(jax.random.key(key), len(leaves))

    def one(path, a, k):
        name = path[-1].key
        if name == "scale":
            return a * (1.0 + 0.3 * jax.random.normal(k, a.shape))
        if name == "gate_bias":
            return 0.1 * jax.random.normal(k, a.shape)
        if name == "gate":
            return a * 4.0      # scores spread over (0, 1)
        if a.ndim == 3:
            return a * a.shape[0] ** 0.5
        return a

    return treedef.unflatten([one(p, a, k)
                              for (p, a), k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def params():
    return seeded(TransformerLM(CFG).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"])


def ids_of(n, seed=0, batch=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, 128, (batch, n)), jnp.int32)


def test_the_layers_differ_and_the_parameters_are_counted(params):
    assert sorted(params) == sorted(
        [f"layer_{i}" for i in range(LAYERS)]
        + ["final_norm", "lm_head", "tok_embed"])
    assert "mlp_gate" in params["layer_0"] and "moe" not in params["layer_0"]
    moe = params["layer_1"]["moe"]
    assert moe["gate"].shape == (48, ROUTER)          # the router is whole
    assert moe["w_in"].shape == (HELD, 48, 24)        # the experts a share
    assert moe["gate_bias"].shape == (ROUTER,)
    assert params["layer_1"]["attn_qkv"]["kernel"].shape == (48, 8 * 16)
    assert params["layer_1"]["q_norm"]["scale"].shape == (16,)
    assert param_count(CFG) == sum(a.size for a in jax.tree.leaves(params))
    assert not CFG.uniform and TransformerConfig().uniform


def test_full_forward_logits_against_the_reference(params):
    ids = ids_of(40, batch=2)
    close(TransformerLM(CFG).apply({"params": params}, ids),
          ref.logits(CONF, params, ids))


def _held_only_gates(scores, bias, top_k, norm_topk, scale=1.0):
    """The wrong share: gates normalised over the chosen experts this
    device holds, not over all the chosen."""
    _, idx = jax.lax.top_k(scores + bias, top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1) * (idx < HELD)
    return gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-20) * scale, idx


def _int8(w):
    """Rounded to int8 per output channel and back."""
    s = jnp.abs(w).max(axis=-2, keepdims=True) / 127.0
    return jnp.round(w / s) * s


VARIANTS = {
    "full_attention_on_window_layers": {"attn_window": 4096},
    "rope_on_global_layers": {"rope_global": True},
    "softmax_router": {"moe_router": "softmax", "moe_select_bias": False},
    "no_scaling_factor": {"moe_routed_scale": 1.0},
    "no_shared_expert": {"moe_shared_dim": 0},
    "no_selection_bias": {"moe_select_bias": False},
    "qk_norm_over_the_projection": {"qk_norm_per_head": False},
    "gates_normalised_over_held_only": {},
    "int8_experts": {},
}


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_the_tolerance_catches_a_structural_difference(params, variant,
                                                       monkeypatch):
    ids = ids_of(40, batch=2)
    p = params
    if variant == "gates_normalised_over_held_only":
        monkeypatch.setattr(moe_ops, "sigmoid_gates", _held_only_gates)
    if variant == "int8_experts":
        p = jax.tree.map(lambda a: _int8(a) if a.ndim == 3 else a, params)
    if variant == "qk_norm_over_the_projection":
        p = jax.tree_util.tree_map_with_path(
            lambda path, a: jnp.tile(a, {"q_norm": 4, "k_norm": 2}[
                path[-2].key]) if path[-2].key in ("q_norm", "k_norm")
            else a, params)
    got = TransformerLM(dataclasses.replace(CFG, **VARIANTS[variant])).apply(
        {"params": p}, ids)
    assert error(got, ref.logits(CONF, params, ids)) > 20 * RTOL


@pytest.mark.parametrize("ring", [0, WINDOW + 3, 2 * WINDOW],
                         ids=["ring_is_window", "ring_window_plus_3",
                              "ring_two_windows"])
def test_prefill_then_decode_past_several_windows(params, ring):
    """The path ``generate`` runs (decode-mode model, one cache whose
    window layers are rings): prefill 13 tokens (more than a window,
    so the first write wraps), then 27 single-token steps
    teacher-forced, two rows; every position's logits against the
    reference's full forward pass."""
    ids = ids_of(40, seed=3, batch=2)
    P = 13
    model = TransformerLM(dataclasses.replace(CFG, decode=True,
                                              window_ring=ring))
    cache = model.init(jax.random.key(0), ids[:, :1],
                       positions=jnp.zeros((2, 1), jnp.int32))["cache"]
    cache = jax.tree.map(jnp.zeros_like, cache)
    for i in range(LAYERS):
        want = (ring or WINDOW) if CFG.attn_kind(i) == "window" else 96
        assert cache[f"layer_{i}"]["cached_key"].shape == (2, 2, 16, want)
    out, mut = model.apply(
        {"params": params, "cache": cache}, ids[:, :P],
        positions=jnp.broadcast_to(jnp.arange(P), (2, P)), mutable=["cache"])
    rows = [out]
    for t in range(P, 40):
        step, mut = model.apply(
            {"params": params, "cache": mut["cache"]}, ids[:, t:t + 1],
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


def recount(params, seq):
    """The host's own count over ``seq``: pairs that landed on held
    experts, summed over the sparse layers (the reference's router)."""
    chosen = ref.forward(CONF, params, jnp.asarray([seq], jnp.int32))[1]
    return int(sum((np.asarray(c) < HELD).sum() for c in chosen.values()))


def test_generate_against_the_reference(params):
    prompt = np.asarray(ids_of(21, seed=5))[0]
    out = np.asarray(generate(CFG, params, jnp.asarray(prompt[None]), 12,
                              temperature=0.0))[0]
    assert shortfall(params, prompt, out) <= RTOL


def engine(params, **kw):
    kw = {"slots": 2, "temperature": 0.0, "top_k": 0, "steps_per_sync": 2,
          "kv_block": 4, "kv_pool_blocks": 97, "prefill_chunk": 16, **kw}
    return ContinuousBatcher(CFG, params, **kw)


def test_engine_bucketed_and_chunked_prefill_against_the_reference(params):
    """Two slots at different positions: a 40-token prompt through the
    chunked prefill (chunk 16: two mid chunks and a padded final one,
    each several windows long) and a 5-token prompt, shorter than a
    window, through a padded bucket, decoding together for 15 tokens
    (14 fed back: whole ticks of 2, so the program runs no token step
    past a request's end and the recount below is exact): every ring
    wraps."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 128, (n,)).astype(np.int32) for n in (40, 5)]
    eng = engine(params)
    try:
        futs = [eng.submit(p, 15) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["chunked_admissions"] == 1 and stats["prefill_chunks"] == 3
    for p, out in zip(prompts, outs):
        assert len(out) == 15
        assert shortfall(params, p, out) <= RTOL
    assert stats["moe_prefill_drops"] == 0
    # what the routers routed is the host's own arithmetic; what this
    # share computed is the host's recount with the reference's router
    assert stats["moe_tokens"] == 45 + 2 * 14
    assert stats["moe_assignments_routed"] == (
        TOP_K * SPARSE * stats["moe_tokens"])
    assert stats["moe_assignments"] == sum(
        recount(params, list(p) + list(out[:-1]))
        for p, out in zip(prompts, outs))
    assert 0 < stats["moe_assignments"] < stats["moe_assignments_routed"]


def test_a_prefix_pool_hit_on_a_second_turn(params):
    """A conversation's second turn starts from the pool: the global
    layers from the chain's blocks, the window layers from the
    snapshot of the last window before the chain's end.  Its answer is
    held to the reference like any other, and it is the answer of an
    engine that has no pool."""
    rng = np.random.default_rng(11)
    p1 = rng.integers(1, 128, (27,)).astype(np.int32)
    eng = engine(params)
    try:
        out1 = eng.submit(p1, 9, session="s").result(300)
        first = eng.stats()
        p2 = np.concatenate([p1, out1, rng.integers(1, 128, (6,))]).astype(
            np.int32)
        out2 = eng.submit(p2, 11, session="s").result(300)
        stats = eng.stats()
    finally:
        eng.stop()
    # one where the prompt ended (24 of 27 tokens), one where the
    # committed sequence did (32 of 35)
    assert first["kv_window_snapshots"] == 2
    assert stats["kv_prefix_hits"] == 1, stats
    # prompt + all but the last of the answer, in whole blocks of 4
    assert stats["kv_prefill_tokens_skipped"] == (27 + 8) // 4 * 4
    assert shortfall(params, p1, out1) <= RTOL
    assert shortfall(params, p2, out2) <= RTOL
    cold = engine(params, kv_block=0)
    try:
        np.testing.assert_array_equal(out2, cold.generate(p2, 11, 300))
    finally:
        cold.stop()


def test_the_same_prompt_again_starts_from_its_own_snapshot(params):
    """The snapshot taken when a prompt's prefill ends: the deepest
    block edge the same prompt can match, so a prompt that comes again
    (the benchmark's pooled probe) prefills its last tokens only."""
    rng = np.random.default_rng(17)
    p = rng.integers(1, 128, (43,)).astype(np.int32)
    eng = engine(params)
    try:
        cold = eng.generate(p, 9, 300)
        pooled = eng.generate(p, 9, 300)
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["kv_prefix_hits"] == 1
    assert stats["kv_prefill_tokens_skipped"] == 42 // 4 * 4
    np.testing.assert_array_equal(cold, pooled)
    assert shortfall(params, p, pooled) <= RTOL


def test_a_chain_without_its_snapshot_is_not_reused(params):
    """The snapshot pool is an LRU of its own: a chain whose tail lost
    its snapshot still holds the global layers' blocks, and is not a
    prefix a window layer could resume from."""
    rng = np.random.default_rng(13)
    p1 = rng.integers(1, 128, (21,)).astype(np.int32)
    eng = engine(params)
    try:
        out1 = eng.generate(p1, 5, 300)
        kv = eng._kv
        owners = [nd for nd in kv._nodes if nd.snap]
        turn2 = list(p1) + list(out1[:-1]) + [1]
        assert len(kv.match(turn2)) == (21 + 4) // 4       # the tail's
        assert len(kv.match(list(p1) + [1])) == 20 // 4    # the prompt's
        # a prefix that ends at neither has no window to start from
        assert kv.match(list(p1[:17])) == []
        assert len(owners) == 2
        for nd in owners:
            kv._snap_free.append(kv._drop_snap(nd))
        assert kv.match(turn2) == [] == kv.match(list(p1) + [1])
        p2 = np.concatenate([p1, out1]).astype(np.int32)
        out2 = eng.generate(p2, 4, 300)
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["kv_prefix_hits"] == 0
    assert shortfall(params, p2, out2) <= RTOL


def test_the_counters_against_a_host_recount(params):
    """One request, one token step a sync: every counter is exact."""
    prompt = np.asarray(ids_of(19, seed=9))[0]
    eng = engine(params, slots=3, steps_per_sync=1, kv_block=0,
                 prefill_chunk=0)
    try:
        out = eng.generate(prompt, 15, timeout=300)
        stats = eng.stats()
    finally:
        eng.stop()
    fed = 14                                   # tokens fed back
    assert stats["moe_tokens"] == 19 + fed
    assert stats["moe_assignments_routed"] == TOP_K * SPARSE * (19 + fed)
    assert stats["moe_assignments"] == recount(
        params, list(prompt) + list(out[:-1]))
    assert stats["moe_decode_layer_steps"] == SPARSE * fed
    assert stats["moe_prefill_groups"] == SPARSE
    assert stats["moe_prefill_drops"] == 0
    # a decode step reads a slot that holds 20, 21, ... positions; a
    # window layer needs min(that, 8) of them and, off the chip, reads
    # its ring: window + kv_block (1 without a pool) + steps_per_sync - 1
    assert stats["decode_kv_tokens_window_need"] == WINDOW * fed
    assert stats["decode_kv_tokens_window_read"] == (WINDOW + 1) * fed
    assert stats["decode_kv_tokens_live"] == sum(range(20, 20 + fed))


@pytest.mark.parametrize("max_len", [64, 96, 4096])
def test_a_window_layers_slot_state_does_not_grow_with_max_len(
        params, max_len, monkeypatch):
    """``stats()`` and ``_require_fit`` (through a device that reports
    a limit) both say so: 6 window layers hold a ring of window +
    kv_block + steps_per_sync - 1 positions whatever ``max_len`` is;
    the 2 global layers hold ``max_len``."""
    ring = WINDOW + 4 + 2 - 1
    per_position = 2 * 2 * 16 * 4              # K and V, 2 heads x 16, f32

    class _Chip:
        device_kind = "toy chip"

        def memory_stats(self):
            return {"bytes_limit": 1 << 16, "bytes_in_use": 0}

    eng = engine(params, max_len=max_len)
    try:
        stats = eng.stats()
        monkeypatch.setattr(jax, "devices", lambda: [_Chip()])
        with pytest.raises(ValueError) as err:
            eng._require_fit(2, 4, 97, 5)
    finally:
        eng.stop()
    assert stats["kv_slot_bytes_window"] == 6 * ring * per_position
    assert stats["kv_slot_bytes_global"] == 2 * max_len * per_position
    assert f"6 window layers hold a ring of {ring}" in str(err.value)
    assert "5 window snapshots" in str(err.value)


@pytest.mark.parametrize("what", ["spec_k", "mesh"])
def test_what_cannot_serve_a_window_refuses_at_construction(params, what):
    if what == "spec_k":
        kw = {"spec_k": 2, "draft_cfg": CFG, "draft_params": params}
        reason = "rewinds the cache index"
    else:
        from edl_tpu.parallel.mesh import MeshSpec, build_mesh
        kw = {"mesh": build_mesh(MeshSpec(dp=1, tp=2), jax.devices()[:2])}
        reason = "no sharded gather"
    with pytest.raises(ValueError, match=reason):
        engine(params, **kw)


# -- the expert layer alone --------------------------------------------------

def moe_layer(**kw):
    return MoEMLP(**{
        "num_experts": ROUTER, "mlp_dim": 24, "top_k": TOP_K,
        "dtype": jnp.float32, "gated": True, "capacity_factor": 0.0,
        "norm_topk": True, "router": "sigmoid", "select_bias": True,
        "routed_scale": 2.5, "shared_dim": 24, "held": 0, **kw})


@pytest.fixture(scope="module")
def layer():
    """An UNCUT layer (all 16 experts) and its input."""
    x = jax.random.normal(jax.random.key(2), (2, 24, 48))
    return seeded(moe_layer().init(jax.random.key(3), x)["params"], 4), x


def share_of(p, s):
    """Share ``s`` of the uncut layer as the program holds it: the
    matrices of experts 4s .. 4s+3, and the router's columns turned so
    that those experts are 0 .. 3 (the program's share is always the
    first ``held`` of its router)."""
    turn = np.roll(np.arange(ROUTER), -HELD * s)
    cut = {k: p[k][HELD * s:HELD * (s + 1)]
           for k in ("w_gate", "w_in", "w_out")}
    return {**p, **cut, "gate": p["gate"][:, turn],
            "gate_bias": p["gate_bias"][turn]}


UNCUT = dict(CONF, num_experts=ROUTER)


def test_the_four_shares_add_up_to_the_uncut_layer(layer):
    """Expert parallelism without its exchange: each share routes over
    all 16, weighs by gates normalised over all the chosen, computes
    the pairs that land on its 4 experts, and adds the shared expert.
    The four partial sums, with the shared expert counted once, are the
    uncut reference's layer - in the program and in the reference."""
    p, x = layer
    flat = x.reshape(-1, 48)
    want, chosen = ref.moe_mlp(UNCUT, p, flat)
    shared = ref._gated(flat, p["shared_gate"]["kernel"],
                        p["shared_in"]["kernel"], p["shared_out"]["kernel"])
    parts, pairs = [], 0
    for s in range(4):
        (y, _), m = moe_layer(held=HELD).apply(
            {"params": share_of(p, s)}, x, mutable=["intermediates"])
        parts.append(y.reshape(-1, 48))
        stats = np.asarray(m["intermediates"]["moe_stats"])
        assert stats[3] == TOP_K * flat.shape[0]          # pairs routed
        assert stats[0] == int(((chosen >= HELD * s)
                                & (chosen < HELD * (s + 1))).sum())
        pairs += stats[0]
        close(y.reshape(-1, 48), ref.moe_mlp(
            UNCUT, {**p, **{k: p[k][HELD * s:HELD * (s + 1)] for k in
                            ("w_gate", "w_in", "w_out")}}, flat,
            held=(HELD * s, HELD * (s + 1)))[0])
    assert pairs == TOP_K * flat.shape[0]
    close(sum(parts) - 3 * shared, want)
    # one share alone is not the layer
    assert error(parts[0], want) > 0.1


@pytest.mark.parametrize("held", [0, HELD], ids=["uncut", "one_share"])
def test_expert_layer_loss_and_gradients_against_the_reference(layer, held):
    """Through the training forward (no decode flag, the aux loss
    computed): loss and every gradient, the selection bias's excepted
    (it chooses and does not weigh: its gradient is zero on both
    sides)."""
    p, x = layer
    p = share_of(p, 0) if held else p
    conf = CONF if held else UNCUT
    target = jax.random.normal(jax.random.key(4), x.shape)

    def loss_sys(p, x):
        y, aux = moe_layer(held=held).apply({"params": p}, x)
        assert aux.shape == ()
        return jnp.mean(jnp.square(y - target))

    def loss_ref(p, x):
        y = ref.moe_mlp(conf, p, x.reshape(-1, 48))[0].reshape(x.shape)
        return jnp.mean(jnp.square(y - target))

    (l_s, g_s) = jax.value_and_grad(loss_sys, argnums=(0, 1))(p, x)
    (l_r, g_r) = jax.value_and_grad(loss_ref, argnums=(0, 1))(p, x)
    close(l_s, l_r)
    flat_s = dict(jax.tree_util.tree_flatten_with_path(g_s)[0])
    for path, want in jax.tree_util.tree_flatten_with_path(g_r)[0]:
        got = flat_s[path]
        if getattr(path[-1], "key", None) == "gate_bias":
            assert float(jnp.abs(got).max()) == float(jnp.abs(want).max()) == 0
            continue
        close(got, want)
        assert float(jnp.abs(got).max()) > 0


def test_a_padded_prompt_routes_exactly_as_the_unpadded_one(layer):
    p, x = layer
    n = 15
    mask = jnp.broadcast_to(jnp.arange(24)[None, :] < n, (2, 24))
    (y_pad, _), m_pad = moe_layer(held=HELD).apply(
        {"params": share_of(p, 0)}, x, mask, mutable=["intermediates"])
    (y_cut, _), m_cut = moe_layer(held=HELD).apply(
        {"params": share_of(p, 0)}, x[:, :n], mutable=["intermediates"])
    np.testing.assert_allclose(y_pad[:, :n], y_cut, rtol=0, atol=1e-6)
    assert float(jnp.abs(y_pad[:, n:]).max()) == 0.0   # pads get nothing
    np.testing.assert_array_equal(m_pad["intermediates"]["moe_stats"],
                                  m_cut["intermediates"]["moe_stats"])
    assert m_cut["intermediates"]["moe_stats"][3] == TOP_K * n * 2


@pytest.mark.parametrize("wrong", [
    {"capacity_factor": 1.25}, {"router": "softmax"}, {"router": "tanh"}])
def test_the_expert_layer_refuses_what_it_cannot_compute(layer, wrong):
    p, x = layer
    with pytest.raises(ValueError):
        moe_layer(**wrong).apply({"params": p}, x)


def test_the_training_forward_unrolls_a_mixed_stack(params):
    """``TransformerLM``'s training forward: with the aux loss, with
    remat, and the same logits either way."""
    ids = ids_of(24, batch=2)
    plain, aux = TransformerLM(CFG).apply({"params": params}, ids,
                                          with_aux=True)
    assert aux.shape == () and float(aux) > 0
    remat = TransformerLM(dataclasses.replace(CFG, remat=True)).apply(
        {"params": params}, ids)
    np.testing.assert_allclose(plain, remat, rtol=0, atol=1e-5)

    def loss(p):
        return jnp.mean(jnp.square(TransformerLM(CFG).apply(
            {"params": p}, ids)))

    grads = jax.grad(loss)(params)
    assert float(jnp.abs(grads["layer_0"]["mlp_in"]["kernel"]).max()) > 0
    assert float(jnp.abs(grads["layer_7"]["moe"]["w_in"]).max()) > 0


@pytest.mark.parametrize("layer", [1, 4, 7])
def test_a_nudge_swaps_two_experts_of_one_token_and_nothing_else(params,
                                                                 layer):
    """``reference(nudge=)``: how a caller has a near-tie resolved the
    other way.  A zero nudge is the plain pass; +1 / -1 on two experts
    of one position swaps exactly those two in that layer's choice
    there, leaves every other (position, layer) choice up to that layer
    and every earlier position's logits as they were, and moves that
    position's logits."""
    ids, at = ids_of(24, seed=5), 17
    plain = ref.reference(CONF, params, ids)
    zero = {layer: jnp.zeros((1, 24, ROUTER), jnp.float32)}
    np.testing.assert_array_equal(
        np.asarray(ref.reference(CONF, params, ids, nudge=zero)["logits"]),
        np.asarray(plain["logits"]))
    chosen = np.asarray(plain["chosen"][layer][0, at])
    out = int(chosen[0])
    into = next(e for e in range(ROUTER) if e not in chosen)
    row = np.zeros((ROUTER,), np.float32)
    row[out], row[into] = -1.0, 1.0
    got = ref.reference(CONF, params, ids, nudge={
        layer: zero[layer].at[0, at].set(row)})
    assert set(np.asarray(got["chosen"][layer][0, at])) == (
        set(chosen) - {out}) | {into}
    for i in range(1, layer + 1):
        same = np.asarray(got["chosen"][i]) == np.asarray(plain["chosen"][i])
        assert same[0, :at].all() and same[0, at + 1:].all()
        assert i == layer or same.all()
    np.testing.assert_array_equal(np.asarray(got["logits"][0, :at]),
                                  np.asarray(plain["logits"][0, :at]))
    assert error(got["logits"][0, at], plain["logits"][0, at]) > 1e-3


def test_the_two_copies_of_the_reference_are_equal(params):
    """``benchmarks/archs/exaone_moe.py`` carries the benchmark's copy,
    and its parameter count is the program's."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "archs", "exaone_moe.py")
    spec = importlib.util.spec_from_file_location("bench_exaone_moe", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    ids = ids_of(29, seed=11, batch=2)
    got, want = bench.reference(CONF, params, ids), ref.reference(
        CONF, params, ids)
    np.testing.assert_array_equal(np.asarray(got["logits"]),
                                  np.asarray(want["logits"]))
    assert sorted(got["chosen"]) == sorted(want["chosen"]) == list(
        range(1, LAYERS))
    for i in want["chosen"]:
        np.testing.assert_array_equal(np.asarray(got["chosen"][i]),
                                      np.asarray(want["chosen"][i]))
        np.testing.assert_array_equal(np.asarray(got["experts"][i][1]),
                                      np.asarray(want["experts"][i][1]))
