"""granite-4.0-h-small's block on the program's normal path against the
plain reference (``tests/helpers/granite_moe_hybrid_reference.py``:
float32, the recurrence one token at a time, no chunking, no kernels,
no cache, no sort), at a toy size on the CPU: 10 layers in the
published pattern (five Mamba-2 layers, the attention layer at 5, four
more), hidden 32, 4 state heads of 16 with a state of 8, convolution 4,
scan chunk 8, 4 query heads over 2 KV heads, 8 experts top-3 of width
16 beside a shared MLP of 24, ONE SHARE of two (this "device" holds
experts 0-3), vocabulary 64, the four multipliers as published.  The
system computes in float32 here so that it routes as the reference does.

TOLERANCE: 1e-4 relative (of the largest reference magnitude), as
``test_olmoe.py`` and ``test_exaone_moe.py`` have it and for their
reasons: both sides are float32 but not the same sums (the chunked scan
against the token-by-token recurrence; rows sorted by expert against
every held expert on every token; a cache against a full pass).
Measured here: 1e-7 to 4e-6.  Every structural variant below is 1e-3 or
more.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models import transformer
from edl_tpu.models.transformer import (TransformerConfig, TransformerLM,
                                        lm_loss, lm_loss_fused, param_count)
from edl_tpu.ops import moe as moe_ops
from edl_tpu.ops.moe import MoEMLP
from edl_tpu.serving.engine import ContinuousBatcher
from tests.helpers import granite_moe_hybrid_reference as ref

RTOL = 1e-4
LAYERS, ROUTER, HELD, TOP_K, CHUNK, BLOCK = 10, 8, 4, 3, 8, 4
TYPES = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
CONF = {"hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
        "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 8,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": CHUNK,
        "num_local_experts": HELD, "router_experts": ROUTER,
        "num_experts_per_tok": TOP_K, "intermediate_size": 16,
        "shared_intermediate_size": 24, "num_hidden_layers": LAYERS,
        "vocab_size": 64, "rms_norm_eps": 1e-5, "layer_types": TYPES,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "attention_multiplier": 0.0078125, "logits_scaling": 16}
CFG = TransformerConfig(
    vocab_size=64, num_layers=LAYERS, embed_dim=32, num_heads=4,
    num_kv_heads=2, mlp_dim=16, moe_mlp_dim=16, max_len=96,
    dtype=jnp.float32, remat=False, attention_impl="dense", norm_eps=1e-5,
    layer_attn=tuple("ssm" if t == "mamba" else "global" for t in TYPES),
    rope_global=False, tie_embeddings=True, moe_experts=ROUTER,
    moe_held=HELD, moe_top_k=TOP_K, moe_capacity=0.0, moe_gated=True,
    moe_norm_topk=True, moe_shared_dim=24, ssm_heads=4, ssm_head_dim=16,
    ssm_state=8, ssm_groups=1, ssm_conv=4, ssm_chunk=CHUNK,
    embed_multiplier=12.0, residual_multiplier=0.22, attn_scale=0.0078125,
    logits_scaling=16.0)
MAMBA = LAYERS - 1


def error(got, want):
    want = np.asarray(want)
    return np.abs(np.asarray(got) - want).max() / np.abs(want).max()


def close(got, want, rtol=RTOL):
    assert np.shape(got) == np.shape(want)
    err = error(got, want)
    assert err <= rtol, f"relative error {err:.2e} over {rtol:.0e}"


def seeded(tree, key=1):
    """Weights a comparison can see through: norm scales, D and the
    convolution moved off their defaults, each expert matrix
    lecun-normal by itself (PERF.md section 6, PR 26), and embedding
    rows small enough that the tied head's best logit is the layers'
    doing and not the input token's own row."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    keys = jax.random.split(jax.random.key(key), len(leaves))

    def fix(path, a, k):
        name = path[-1].key
        normal = jax.random.normal(k, a.shape, jnp.float32)
        if name in ("scale", "D"):
            return 1.0 + 0.1 * normal
        if name == "conv_w":
            return 0.5 * normal
        if name == "conv_b":
            return 0.1 * normal
        if name == "embedding":
            return 0.02 * normal
        if a.ndim == 3:
            return a * a.shape[0] ** 0.5
        return a

    return treedef.unflatten([fix(p, a, k) for (p, a), k in zip(leaves, keys)])


def ids_of(length, seed=3, batch=1):
    return jax.random.randint(jax.random.key(seed), (batch, length), 1, 64)


@pytest.fixture(scope="module")
def params():
    return seeded(TransformerLM(CFG).init(jax.random.key(0),
                                          ids_of(8))["params"])


def engine(params, cfg=CFG, **kw):
    kw = dict(dict(slots=3, max_len=96, temperature=0.0, steps_per_sync=4,
                   kv_block=BLOCK, kv_pool_blocks=96, prefill_chunk=16,
                   prefill_buckets=(8, 16, 32)), **kw)
    return ContinuousBatcher(cfg, params, **kw)


def greedy(params, prompt, n, conf=CONF):
    """The reference's own continuation, one full pass a token."""
    ids = list(prompt)
    for _ in range(n):
        ids.append(int(ref.logits(conf, params, jnp.asarray([ids]))[0, -1]
                       .argmax()))
    return ids[len(prompt):]


def served(eng, prompt, n, **kw):
    return eng.submit(np.asarray(prompt, np.int32), n, **kw).result(
        300).tolist()


def judged(params, prompt, out):
    """The served tokens at the level of logits: teacher-forced on the
    served answer, the reference's best logit at every answer position
    IS the served token's (float32: no near-tie is in reach of 1e-4)."""
    ids = jnp.asarray([list(prompt) + list(out)])[:, :-1]
    at = np.asarray(ref.logits(CONF, params, ids))[0, len(prompt) - 1:]
    assert (at.max(-1) - at[np.arange(len(out)), out]).max() <= RTOL * np.abs(
        at).max()


# -- the block -----------------------------------------------------------------

@pytest.mark.parametrize("length", [5, 16, 21, 24])
def test_full_forward_equals_the_reference(params, length):
    """Lengths that are (16, 24) and are not (5, 21) multiples of the
    scan chunk: the chunked scan computes the plain recurrence."""
    ids = ids_of(length, batch=2)
    close(TransformerLM(CFG).apply({"params": params}, ids),
          ref.logits(CONF, params, ids))


def test_param_count_is_the_trees(params):
    assert param_count(CFG) == sum(a.size for a in jax.tree.leaves(params))


def decode_model(max_len=64):
    return TransformerLM(dataclasses.replace(CFG, decode=True,
                                             max_len=max_len))


def fresh_cache(model, batch):
    return jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((batch, 1), jnp.int32),
                           positions=jnp.zeros((batch, 1), jnp.int32)))["cache"]


def zeros(shapes):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def test_prefill_then_decode_through_the_cache(params):
    """A 13-token prefill (one chunk and a remainder), then 8 one-token
    steps against the cached recurrence: every step's logits equal the
    reference's one full pass at that position."""
    model = decode_model()
    ids = ids_of(21)
    want = ref.logits(CONF, params, ids)
    cache = zeros(fresh_cache(model, 1))
    logits, mut = model.apply(
        {"params": params, "cache": cache}, ids[:, :13],
        positions=jnp.arange(13)[None], mutable=["cache"])
    close(logits, want[:, :13])
    for t in range(13, 21):
        logits, mut = model.apply(
            {"params": params, "cache": mut["cache"]}, ids[:, t:t + 1],
            positions=jnp.full((1, 1), t), mutable=["cache"])
        close(logits[:, 0], want[:, t])


def state_of(cache, lane):
    return {name: {k: np.asarray(v[lane]) for k, v in node["ssm"].items()
                   if k != "cache_index"}
            for name, node in cache.items() if "ssm" in node}


def test_bucketed_prefill_of_unequal_lanes_leaves_each_its_unpadded_state(
        params):
    """Three lanes of 16, 9 and 3 tokens in one 16-wide bucket: a
    lane's recurrent state (and the convolution's last inputs) equals
    its unpadded run's.  With attention a padded position is harmless;
    here it would be a wrong state."""
    eng = engine(params)
    try:
        lens = [16, 9, 3]
        ids = np.zeros((3, 16), np.int32)
        rows = [np.asarray(ids_of(n, seed=20 + n))[0] for n in lens]
        for i, row in enumerate(rows):
            ids[i, :len(row)] = row
        slab, toks, _, snap = eng._prefill_fn(16, 3)(
            eng._params, jnp.asarray(ids), jnp.asarray(lens, jnp.int32),
            jax.random.key(0), jnp.asarray([12, 8, 0], jnp.int32))
        model = decode_model(96)
        for lane, row in enumerate(rows):
            alone = model.apply(
                {"params": params, "cache": zeros(fresh_cache(model, 1))},
                jnp.asarray(row)[None], positions=jnp.arange(len(row))[None],
                mutable=["cache"])
            logits, mut = alone
            got, want = state_of(slab, lane), state_of(mut["cache"], 0)
            assert len(got) == MAMBA
            for name in want:
                for leaf in want[name]:
                    close(got[name][leaf], want[name][leaf])
            assert int(toks[lane]) == int(logits[0, -1].argmax())
        # the snapshot is the state after snap_at tokens: lane 0's at 12
        first12 = model.apply(
            {"params": params, "cache": zeros(fresh_cache(model, 1))},
            jnp.asarray(rows[0][:12])[None], positions=jnp.arange(12)[None],
            mutable=["cache"])[1]["cache"]
        for name, node in state_of(first12, 0).items():
            close(snap[name]["ssm/ssm_state"][0], node["ssm_state"])
            close(snap[name]["ssm/conv_state"][0], node["conv_state"])
        # ... and lane 2's at 0 is the zero state
        assert all(float(jnp.abs(leaf[2]).max()) == 0.0
                   for node in snap.values() for leaf in node.values())
    finally:
        eng.stop()


def test_the_chunk_lane_carries_state_over_three_chunks(params):
    """A 40-token prompt through 16-token chunks (two mid chunks and a
    padded final one), then decoded: tokens and final state are the
    monolithic prefill's."""
    prompt = np.asarray(ids_of(40, seed=5))[0].tolist()
    eng = engine(params)
    try:
        out = served(eng, prompt, 6)
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["chunked_admissions"] == 1 and stats["prefill_chunks"] == 3
    assert out == greedy(params, prompt, 6)
    judged(params, prompt, out)
    # 16 + 16 + a bucket of 8 for the last 8 tokens: no padding
    assert stats["ssm_prefill_positions"] == 40
    assert stats["ssm_prefill_positions_pad"] == 0


def test_a_prompt_that_extends_an_earlier_one_resumes_from_its_snapshot(
        params):
    """The first prompt (22 tokens) leaves a state snapshot at its last
    block edge that the same prompt can match again (20).  A prompt
    that extends it hits there and prefills only the rest.  A prompt
    that extends prompt + ANSWER finds blocks deeper than any snapshot
    (an answer's end is never snapshotted) and is cut back to 20: the
    tokens between are prefilled again, and counted."""
    first = np.asarray(ids_of(22, seed=6))[0].tolist()
    eng = engine(params)
    try:
        answer = served(eng, first, 7)
        assert answer == greedy(params, first, 7)
        s0 = eng.stats()
        assert s0["kv_state_snapshots"] == 1
        longer = first + np.asarray(ids_of(9, seed=7))[0].tolist()
        out = served(eng, longer, 5)
        s1 = eng.stats()
        assert out == greedy(params, longer, 5)
        judged(params, longer, out)
        assert s1["kv_prefix_hits"] - s0["kv_prefix_hits"] == 1
        assert (s1["kv_prefill_tokens_skipped"]
                - s0["kv_prefill_tokens_skipped"]) == 20
        assert s1["kv_state_reprefill_tokens"] == 0
        # prompt + answer (28 tokens committed = 7 blocks), extended
        deeper = first + answer + [5, 6, 7]
        out = served(eng, deeper, 4)
        s2 = eng.stats()
        assert out == greedy(params, deeper, 4)
        assert (s2["kv_prefill_tokens_skipped"]
                - s1["kv_prefill_tokens_skipped"]) == 20
        assert s2["kv_state_reprefill_tokens"] == 28 - 20
    finally:
        eng.stop()


def test_a_slot_freed_and_taken_again_starts_from_zero_state(params):
    """One slot, two requests: the second is served as if alone (its
    prefill's state replaces the slot's whole), and a request whose
    budget ends in the middle of a 4-step program (6 = 1 + 4 + 1)
    leaves nothing behind that the next one sees."""
    eng = engine(params, slots=1)
    try:
        for seed, n in ((8, 6), (9, 7), (10, 3)):
            prompt = np.asarray(ids_of(11, seed=seed))[0].tolist()
            out = served(eng, prompt, n)
            assert out == greedy(params, prompt, n)
            judged(params, prompt, out)
    finally:
        eng.stop()


def test_a_step_leaves_free_slots_state_untouched(params):
    """Slot 2's state is set to a marker; a request decodes in another
    slot through several step programs; the marker stands."""
    eng = engine(params)
    try:
        def mark(cache):
            return jax.tree.map(
                lambda a: a.at[2].set(jnp.ones_like(a[2]))
                if a.ndim > 1 else a, cache)
        eng._cache = eng.run_on_engine(lambda: mark(eng._cache))
        prompt = np.asarray(ids_of(9, seed=12))[0].tolist()
        assert served(eng, prompt, 9) == greedy(params, prompt, 9)
        for node in state_of(eng._cache, 2).values():
            for leaf in node.values():
                assert (leaf == 1).all()
    finally:
        eng.stop()


def test_session_export_and_import_carry_the_state_snapshot(params):
    prompt = np.asarray(ids_of(27, seed=13))[0].tolist()
    a = engine(params)
    answer = served(a, prompt, 6, session="s")
    assert a.drain(60)
    exported = a.export_sessions()
    assert len(exported) == 1
    session, tokens, meta, blob = exported[0]
    # down to the deepest node that owns a snapshot: the prompt's edge
    assert len(tokens) == 24 and meta["state_layers"]
    b = engine(params)
    try:
        assert b.import_session(session, tokens, meta, blob) == 6
        nxt = prompt + answer + [9, 8, 7]
        s0 = b.stats()
        out = served(b, nxt, 5, session="s")
        s1 = b.stats()
        assert out == greedy(params, nxt, 5)
        assert (s1["kv_prefill_tokens_skipped"]
                - s0["kv_prefill_tokens_skipped"]) == 24
    finally:
        b.stop()


@pytest.mark.parametrize("max_len", [64, 96, 4096])
def test_a_state_space_layers_slot_state_does_not_grow_with_max_len(
        params, max_len):
    eng = engine(params, max_len=max_len, kv_pool_blocks=8)
    try:
        stats = eng.stats()
    finally:
        eng.stop()
    per_layer = 4 * 16 * 8 * 4 + 3 * (64 + 16) * 4     # S f32; conv f32 here
    assert stats["kv_slot_bytes_state"] == MAMBA * per_layer
    assert stats["kv_slot_bytes_global"] == 2 * 2 * 8 * 4 * max_len
    assert stats["kv_slot_bytes_window"] == 0


@pytest.mark.parametrize("path", ["einsum", "kernel"])
def test_the_counters_are_the_hosts_recount(params, path, monkeypatch):
    """One request alone: 10-token prompt in a 16 bucket, 9 tokens out =
    the prefill's and 8 steps = 2 programs of 4.  ``ssm_state_steps_run``
    is counted by the step program itself: every slot on the einsum
    path (this CPU), the live slot alone under the kernel's fetch plan
    (here in interpret mode)."""
    if path == "kernel":
        from edl_tpu.ops import ssm
        monkeypatch.setattr(ssm, "applies",
                            lambda L, mesh: L == 1 and mesh is None)
    eng = engine(params)
    try:
        served(eng, np.asarray(ids_of(10, seed=14))[0].tolist(), 9)
        s = eng.stats()
    finally:
        eng.stop()
    assert s["ssm_state_steps"] == 1 * 8 * MAMBA
    assert s["ssm_state_steps_run"] == (
        3 if path == "einsum" else 1) * 8 * MAMBA
    assert s["ssm_prefill_positions"] == 16
    assert s["ssm_prefill_positions_pad"] == 6
    assert s["moe_assignments_routed"] == TOP_K * LAYERS * s["moe_tokens"]
    assert s["moe_tokens"] == 10 + 8
    assert s["kv_state_snapshots"] == 1 and s["kv_state_snapshot_skips"] == 0


@pytest.mark.parametrize("what", ["spec_k", "mesh"])
def test_what_cannot_serve_a_recurrence_refuses_at_construction(params, what):
    if what == "spec_k":
        kw = dict(spec_k=2, draft_cfg=CFG, draft_params=params)
        reason = "cannot be rewound"
    else:
        from jax.sharding import Mesh
        kw = dict(mesh=Mesh(np.asarray(jax.devices()[:2]), ("tp",)))
        reason = "no sharding yet"
    with pytest.raises(ValueError, match=reason):
        ContinuousBatcher(CFG, params, slots=2, max_len=64, temperature=0.0,
                          **kw)


# -- the two shares ---------------------------------------------------------------

def test_the_two_shares_sum_to_the_uncut_layer(params):
    """Expert parallelism without its exchange: share 0 (experts 0-3)
    and share 1 (experts 4-7), each with the router whole and the gates
    normalised over all the chosen, add up, the shared MLP counted
    once, to the uncut reference's layer; in the reference and in the
    program alike."""
    key = jax.random.key(21)
    whole = dict(params["layer_1"]["moe"])
    for name in ("w_gate", "w_in", "w_out"):
        extra = jax.random.normal(jax.random.fold_in(key, len(name)),
                                  whole[name].shape) * 0.2
        whole[name] = jnp.concatenate([whole[name], extra], 0)   # 8 experts
    y = jax.random.normal(key, (1, 19, 32))
    flat = y.reshape(19, 32)
    uncut = ref.moe_mlp(dict(CONF, num_local_experts=ROUTER), whole, flat)[0]
    shared = ref._gated(flat, *(whole[n]["kernel"] for n in
                                ("shared_gate", "shared_in", "shared_out")))

    def share(lo, hi):      # the tree holds the share's matrices alone
        p = dict(whole, **{n: whole[n][lo:hi]
                           for n in ("w_gate", "w_in", "w_out")})
        return ref.moe_mlp(CONF, p, flat, held=(lo, hi))[0]

    lo, hi = share(0, 4), share(4, 8)
    close(lo + hi - shared, uncut)

    def program(share):
        # the program holds experts 0 .. held - 1: share 1 is the same
        # layer with the router's columns and the experts rolled by 4
        p = dict(whole, gate=jnp.roll(whole["gate"], -4 * share, axis=1))
        for name in ("w_gate", "w_in", "w_out"):
            p[name] = jnp.roll(whole[name], -4 * share, axis=0)[:HELD]
        layer = MoEMLP(num_experts=ROUTER, mlp_dim=16, top_k=TOP_K,
                       capacity_factor=0.0, dtype=jnp.float32, gated=True,
                       norm_topk=True, shared_dim=24, held=HELD)
        return layer.apply({"params": p}, y, mutable=["intermediates"])[0][0]

    close((program(0) + program(1)).reshape(19, 32) - shared, uncut)


# -- what must NOT pass ------------------------------------------------------------

def _held_only(probs, top_k, norm_topk):
    vals, idx = jax.lax.top_k(probs, top_k)
    vals = vals * (idx < HELD)
    return vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-20), idx


def _zeroed(name):
    def change(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, a: jnp.zeros_like(a) if path[-1].key == name else a,
            params)
    return change


VARIANTS = {
    "rope_on_the_attention_layer": dict(cfg=dict(rope_global=True)),
    "scale_one_over_sqrt_head": dict(cfg=dict(attn_scale=0.0)),
    "no_residual_multiplier": dict(cfg=dict(residual_multiplier=1.0)),
    "logits_not_divided": dict(cfg=dict(logits_scaling=1.0)),
    "no_embedding_multiplier": dict(cfg=dict(embed_multiplier=1.0)),
    "no_d_skip": dict(params=_zeroed("D")),
    "norm_before_the_gate": dict(patch=(
        transformer, "_gate_norm",
        lambda o, z, norm: norm(o) * jax.nn.silu(z))),
    "no_conv_bias": dict(params=_zeroed("conv_b")),
    "no_dt_bias": dict(params=_zeroed("dt_bias")),
    "softmax_over_all_without_renormalising": dict(
        cfg=dict(moe_norm_topk=False)),
    "gates_normalised_over_held_only": dict(patch=(
        moe_ops, "top_k_gates", _held_only)),
    "no_shared_mlp": dict(cfg=dict(moe_shared_dim=0)),
}


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_a_structural_variant_breaks_the_tolerance(params, name, monkeypatch):
    change = VARIANTS[name]
    ids = ids_of(21, batch=2)
    want = ref.logits(CONF, params, ids)
    if "patch" in change:
        monkeypatch.setattr(*change["patch"])
    cfg = dataclasses.replace(CFG, **change.get("cfg", {}))
    got = TransformerLM(cfg).apply(
        {"params": change.get("params", lambda p: p)(params)}, ids)
    assert error(got, want) > 10 * RTOL, name


def test_state_carried_in_bfloat16_breaks_the_tolerance(params):
    """The nearest precision below the stated one for the recurrent
    state: every step rounds S to 8 bits of mantissa."""
    cfg = dataclasses.replace(CFG, decode=True, max_len=64,
                              ssm_state_dtype=jnp.bfloat16)
    ids = ids_of(21)
    want = ref.logits(CONF, params, ids)
    model = TransformerLM(cfg)
    cache = zeros(fresh_cache(model, 1))
    worst = 0.0
    for t in range(21):
        logits, mut = model.apply(
            {"params": params, "cache": cache}, ids[:, t:t + 1],
            positions=jnp.full((1, 1), t), mutable=["cache"])
        cache = mut["cache"]
        worst = max(worst, error(logits[:, 0], want[:, t]))
    # 21 tokens at state 8: 1e-3; the limit on the chip is the cell's
    assert worst > 5 * RTOL


# -- the training forward ------------------------------------------------------------

def test_loss_and_gradients_of_a_mamba_layer_equal_the_references(params):
    p = params["layer_0"]["ssm"]
    y = jax.random.normal(jax.random.key(31), (2, 19, 32))
    tgt = jax.random.normal(jax.random.key(32), (2, 19, 32))

    def mine(p):
        return jnp.mean(jnp.square(transformer.Mamba2Mixer(CFG).apply(
            {"params": p}, y) - tgt))

    def theirs(p):
        with jax.default_matmul_precision("highest"):
            return jnp.mean(jnp.square(ref.mamba_mixer(CONF, p, y)[0] - tgt))

    (lm, gm), (lt, gt) = (jax.value_and_grad(f)(p) for f in (mine, theirs))
    close(lm, lt)
    for a, b in zip(jax.tree.leaves(gm), jax.tree.leaves(gt)):
        close(a, b, 1e-3)


def test_loss_and_gradients_of_the_model_equal_the_references(params):
    ids = ids_of(22, seed=33, batch=2)
    inputs, targets = ids[:, :-1], ids[:, 1:]

    def mine(p):
        return lm_loss(TransformerLM(CFG).apply({"params": p}, inputs),
                       targets)

    def theirs(p):
        return lm_loss(ref.logits(CONF, p, inputs), targets)

    (lm, gm), (lt, gt) = (jax.value_and_grad(f)(params)
                          for f in (mine, theirs))
    close(lm, lt)
    flat_m = jax.tree_util.tree_leaves_with_path(gm)
    for (path, a), b in zip(flat_m, jax.tree.leaves(gt)):
        if np.abs(np.asarray(b)).max() > 0:
            close(a, b, 1e-3)
    # the fused loss divides its logits by logits_scaling too
    hidden = TransformerLM(CFG).apply({"params": params}, inputs,
                                      return_hidden=True)
    close(lm_loss_fused(params, hidden, targets, CFG, block_size=32), lt, 1e-5)


# -- the benchmark's copy --------------------------------------------------------------

def bench_arch():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "archs",
        "granite_moe_hybrid.py")
    spec = importlib.util.spec_from_file_location("bench_granite", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


@pytest.mark.parametrize("kept", ["float32", "bfloat16"])
def test_the_state_a_mixers_cache_carries_is_the_recurrences(params, kept):
    """What the benchmark holds ``run.ssm_state_dtype`` by
    (``program_state``: one chunk of the scan, then one-token updates
    of the cached state): in float32 the reference recurrence's final
    state; kept in bfloat16, rounded at every update, far from it."""
    bench = bench_arch()
    p = params["layer_0"]["ssm"]
    y = jax.random.normal(jax.random.key(41), (1, 29, 32))
    with jax.default_matmul_precision("highest"):
        want = ref.mamba_mixer(CONF, p, y)[1][0]
    got = bench.program_state(dataclasses.replace(
        CFG, ssm_state_dtype=jnp.dtype(kept)), p, y, CHUNK)
    heads = bench.slow_heads(p)
    assert heads.shape == (1,)          # a tenth of four heads: one
    err = bench._rel(got - want, want, axes=(-2, -1))
    assert (err.max() <= RTOL) == (kept == "float32"), err
    assert kept == "float32" or err[np.asarray(heads)].min() > 10 * RTOL


def test_the_two_copies_of_the_reference_are_equal(params):
    """``benchmarks/archs/granite_moe_hybrid.py`` carries the
    benchmark's copy, and its parameter count is the program's."""
    bench = bench_arch()
    ids = ids_of(29, seed=11, batch=2)
    got, want = bench.reference(CONF, params, ids), ref.reference(
        CONF, params, ids)
    np.testing.assert_array_equal(np.asarray(got["logits"]),
                                  np.asarray(want["logits"]))
    for i in range(LAYERS):
        np.testing.assert_array_equal(np.asarray(got["chosen"][i]),
                                      np.asarray(want["chosen"][i]))
        for part in ("experts", "mixers"):
            np.testing.assert_array_equal(np.asarray(got[part][i][1]),
                                          np.asarray(want[part][i][1]))
        np.testing.assert_array_equal(np.asarray(got["experts"][i][2]),
                                      np.asarray(want["experts"][i][2]))
        if TYPES[i] == "mamba":
            np.testing.assert_array_equal(np.asarray(got["mixers"][i][3]),
                                          np.asarray(want["mixers"][i][3]))
    full = dict(CONF, mamba_conv_bias=True)
    assert bench.param_count(full) == param_count(CFG)
    assert bench.state_bytes_per_slot(full, itemsize=4) == MAMBA * (
        4 * 16 * 8 * 4 + 3 * 80 * 4)
