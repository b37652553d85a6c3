"""Solar-Open2's block on the program's normal path against the plain
reference (``benchmarks/archs/solar_open2.py``: float32, the delta rule
one token at a time, the gated attention over every visible row, no
chunking, no kernels, no cache, no sort; nothing of ``edl_tpu`` in it),
at a toy size on the CPU: 4 layers in the published pattern (gated GQA,
KDA, KDA, KDA: one whole period), hidden 32, 4 KDA heads of 16 with
convolution 4 in chunks of 8 and ``beta = 2 sigmoid(b)``, 4 query heads
on 2 KV heads of 16 without positions and with an output gate, 16
sigmoid-routed experts top-4 of width 16 beside a shared one, ONE SHARE
of four (this "device" holds experts 0-3), vocabulary 64.  The system
computes in float32 here so that it routes as the reference does.

TOLERANCE: 1e-4 relative (of the largest reference magnitude), as
``test_kimi_linear.py`` has it and for its reasons.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models.transformer import Block, TransformerLM, param_count
from edl_tpu.ops import decode_attention, kda
from edl_tpu.serving import cache_layout
from edl_tpu.serving.engine import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 1e-4
LAYERS, ROUTER, HELD, TOP_K, CHUNK, BLOCK = 4, 16, 4, 4, 8, 8
CONF = {"model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                               "num_heads": 4, "num_kv_heads": None},
        "hidden_size": 32, "num_hidden_layers": LAYERS,
        "num_attention_heads": 4, "head_dim": 16, "num_key_value_heads": 2,
        "vocab_size": 64, "intermediate_size": 64,
        "moe_intermediate_size": 16, "rms_norm_eps": 1e-5,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3, "gqa_layers": [0, 4, 8],
        "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "n_routed_experts": HELD,
        "router_experts": ROUTER, "n_shared_experts": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "num_experts_per_tok": TOP_K,
        "run": {"compute_dtype": "float32", "param_dtype": "float32",
                "kda_chunk": CHUNK, "prefill_chunk": 16, "long_prefix": 112}}


def bench_arch():
    path = os.path.join(ROOT, "benchmarks", "archs", "solar_open2.py")
    spec = importlib.util.spec_from_file_location("bench_solar", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


ref = bench_arch()
CFG = ref.transformer_config(CONF, max_len=128, remat=False,
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
    p = ref.init_params(CFG, 7, "float32")
    p["tok_embed"]["embedding"] = p["tok_embed"]["embedding"] * 0.1
    return p


def engine(params, cfg=CFG, **kw):
    kw = dict(dict(slots=3, max_len=128, temperature=0.0, steps_per_sync=4,
                   kv_block=BLOCK, kv_pool_blocks=64, prefill_chunk=16,
                   prefill_buckets=(8, 16, 32)), **kw)
    return ContinuousBatcher(cfg, params, **kw)


def shortfall(params, history, tokens):
    """How far the reference's ONE pass over ``history + tokens`` puts
    each served token under its best logit there, in standard
    deviations of the row (0: the reference's own greedy choice)."""
    ids = jnp.asarray([list(history) + list(tokens)[:-1]])
    rows = np.asarray(ref.logits(CONF, params, ids))[0, len(history) - 1:]
    got = rows[np.arange(len(tokens)), list(tokens)]
    return float(((rows.max(-1) - got) / rows.std(-1)).max())


def served(eng, prompt, n, **kw):
    return eng.submit(np.asarray(prompt, np.int32), n, **kw).result(
        300).tolist()


# -- the block -----------------------------------------------------------------

@pytest.mark.parametrize("length", [21])
def test_full_forward_equals_the_reference(params, length):
    """A length that is no multiple of the delta rule's chunk."""
    ids = ids_of(length, batch=2)
    close(TransformerLM(CFG).apply({"params": params}, ids),
          ref.logits(CONF, params, ids))


def test_the_counts_agree(params):
    """The program's count, the benchmark's own from the published keys,
    and the tree; and the real configuration file's 3.31 B."""
    n = sum(a.size for a in jax.tree.leaves(params))
    assert param_count(CFG) == n == ref.param_count(CONF)
    path = os.path.join(ROOT, "benchmarks", "configs",
                        "solar-open2-250b-serve-ep8.json")
    with open(path) as f:
        conf = json.load(f)
    real = ref.transformer_config(conf, max_len=conf["run"]["max_len"])
    assert (param_count(real) == ref.param_count(conf)
            == conf["memory"]["parameters"] == 3_308_353_344)
    assert real.layer_attn == ("global", "kda", "kda", "kda")
    assert (real.embed_dim, real.num_heads, real.kv_heads, real.head_dim,
            real.expert_dim, real.moe_top_k, real.moe_experts, real.moe_held,
            real.kda_conv) == (4096, 64, 8, 128, 1280, 8, 320, 40, 4)
    # the one stack that pairs head rows with delta-rule state
    kinds = [type(c) for c in cache_layout.cache_classes(
        dataclasses.replace(real, decode=True)).values()]
    assert kinds == [cache_layout.HeadRows] + [cache_layout.KdaState] * 3


@pytest.mark.parametrize("what", ["gated", "ungated", "undoubled"])
def test_the_published_switches(params, what):
    """The gated layer against the reference; with the gate off the
    UNGATED reference and not the gated one; beta undoubled likewise."""
    ids = ids_of(12)
    off = {"ungated": {"use_gqa_gate": False},
           "undoubled": {"kda_allow_neg_eigval": False}}.get(what, {})
    conf = {**CONF, **off}
    cfg = ref.transformer_config(conf, max_len=128, remat=False,
                                 attention_impl="dense")
    p = params
    if what == "ungated":
        p = {k: ({n: w for n, w in v.items() if n != "attn_gate"}
                 if k.startswith("layer_") else v) for k, v in params.items()}
    got = TransformerLM(cfg).apply({"params": p}, ids)
    close(got, ref.logits(conf, p, ids))
    if off:
        assert error(got, ref.logits(CONF, params, ids)) > 1e-2


def test_negative_eigenvalues_are_exercised(params):
    """The three forms of the delta rule agree with betas in (1, 2]."""
    B, L, H, R = 2, 21, 4, 16
    ks = jax.random.split(jax.random.key(5), 6)
    q, k, v = (jax.random.normal(a, (B, L, H, R)) for a in ks[:3])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, L, H, R)))
    beta = 1.0 + jax.nn.sigmoid(jax.random.normal(ks[4], (B, L, H)))
    s0 = jnp.zeros((B, H, R, R))
    want, last = kda.kda_recurrence(q, k, v, g, beta, s0)
    got, final, _ = kda.kda_chunked(q, k, v, g, beta, s0, chunk=CHUNK)
    close(got, want)
    close(final, last)
    s, outs = s0, []
    live = jnp.ones((B,), bool)
    for t in range(L):
        o, s = kda.kda_step_reference(s, q[:, t], k[:, t], v[:, t], g[:, t],
                                      beta[:, t], live)
        outs.append(o)
    close(jnp.stack(outs, 1), want)
    close(s, last)


@pytest.mark.parametrize("heads,width,chunk,mapped", [
    (64, 128, 64, True),        # this stack: 128 MiB a lane
    (32, 128, 64, False),       # the benchmark's other delta-rule stack
    (64, 128, 32, False), (4, 16, 8, False)])
def test_lanes_are_mapped_by_the_widths(heads, width, chunk, mapped):
    assert kda.lanes_mapped(heads, width, chunk) == mapped


@pytest.mark.parametrize("lengths,snap_at", [
    ((21, 9, 1), (16, 5, 0)), ((21, 9, 1), None), (None, None)])
def test_mapped_lanes_equal_lanes_side_by_side(monkeypatch, lengths, snap_at):
    """A call's lanes one after another (what 64 heads of 128 take:
    ``ops/kda.lanes_mapped``) give what the lanes side by side give:
    outputs, final states, snapshots, ragged lengths."""
    B, L, H, R = 3, 21, 4, 16
    ks = jax.random.split(jax.random.key(8), 6)
    q, k, v = (jax.random.normal(a, (B, L, H, R)) for a in ks[:3])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, L, H, R)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, L, H)))
    s0 = jax.random.normal(ks[5], (B, H, R, R))
    kw = {"chunk": CHUNK,
          "lengths": None if lengths is None else jnp.asarray(lengths),
          "snap_at": None if snap_at is None else jnp.asarray(snap_at)}
    side = kda.kda_chunked(q, k, v, g, beta, s0, **kw)
    monkeypatch.setattr(kda, "_LANE_BLOCK", 0)
    assert kda.lanes_mapped(H, R, CHUNK)
    mapped = kda.kda_chunked(q, k, v, g, beta, s0, **kw)
    assert (mapped[2] is None) == (snap_at is None)
    for got, want in zip(mapped, side):
        if want is not None:
            close(got, want, 1e-6)


@pytest.mark.parametrize("lanes,offset,width", [(1, 200, 24), (2, 0, 40),
                                                (1, 381, 3)])
def test_tiled_chunk_path_equals_the_dense_one(lanes, offset, width):
    """A ragged prefix: the call's last position inside a tile, tiles
    past it never read (NaNs there), several lanes at one index."""
    H, Hk, D, T = 4, 2, 16, 384
    ks = jax.random.split(jax.random.key(11), 3)
    end = offset + width
    q = jax.random.normal(ks[0], (lanes, width, H, D))
    keys = jax.random.normal(ks[1], (lanes, Hk, D, T))
    vals = jax.random.normal(ks[2], (lanes, Hk, T, D))
    tk = decode_attention.prefix_block(lanes, width, H, T)
    past = -(-end // tk) * tk
    keys = keys.at[..., past:].set(jnp.nan)
    vals = vals.at[:, :, past:].set(jnp.nan)
    q_pos = offset + jnp.broadcast_to(jnp.arange(width), (lanes, width))
    got = decode_attention.prefix_chunk_attention(
        q, keys, vals, q_pos, jnp.asarray(end), scale=D ** -0.5)
    mask = jnp.arange(T)[None, None, :] <= q_pos[:, :, None]
    want = Block._masked_attention(q, jnp.nan_to_num(keys),
                                   jnp.nan_to_num(vals), mask, D ** -0.5)
    close(got, want, 1e-5)


# (tokens a call, query heads, max_len): the widest multi-token call of
# each served head-row stack the benchmark had (the largest dense scores
# are 1.07 GB a lane), and this stack's at every chunk the issue names,
# at its max_len and at the re-cut's
@pytest.mark.parametrize("width,heads,max_len,tiled", [
    (256, 32, 32768, False), (512, 32, 16384, False),
    (2048, 16, 8192, False), (256, 64, 16384, False),
    (1, 64, 114688, False),
    (256, 64, 65536, True), (512, 64, 65536, True), (1024, 64, 65536, True),
    (256, 64, 114688, True), (512, 64, 114688, True),
    (1024, 64, 114688, True)])
def test_the_rule_is_the_shapes(width, heads, max_len, tiled):
    """One lane's dense float32 scores past 2 GiB, and nothing else,
    send a multi-token call to the tiled path; the tile divides the slab
    and keeps its scores and probabilities under 48 MiB."""
    assert decode_attention.prefix_tiled(width, heads, max_len) == tiled
    assert tiled == (width > 1 and 4 * heads * width * max_len > 2 << 30)
    tk = decode_attention.prefix_block(1, width, heads, max_len)
    assert max_len % tk == 0 and tk % 128 == 0
    assert tk == 128 or 6 * heads * width * tk <= 48 << 20


# -- through the engine --------------------------------------------------------

@pytest.fixture(scope="module")
def eng(params):
    e = engine(params)
    yield e
    e.stop()


def test_prefill_then_decode_through_the_cache(params, eng):
    """A chunked admission (40 tokens: two chunks and a remainder) and 9
    tokens decoded through the cache are the reference's one pass."""
    prompt = ids_of(40, seed=9)[0].tolist()
    out = served(eng, prompt, 9)
    assert len(out) == 9 and shortfall(params, prompt, out) == 0.0
    stats = eng.stats()
    assert stats["chunked_admissions"] >= 1
    assert stats["kv_slot_bytes_global"] > 0 < stats["kv_slot_bytes_state"]
    # the dense path reads the slab a call, whatever is live
    assert stats["kv_prefill_rows_read"] > stats["kv_prefill_rows_live"] > 0


def test_a_turn_resumed_from_the_pool(params, eng):
    """Turn 2 carries turn 1's history: it re-attaches the GQA layer's
    blocks and the state snapshot at turn 1's last prompt block edge and
    re-prefills the tail (the answer included: no snapshot lies at an
    answer's end); its tokens are the reference's one pass over the
    whole history."""
    first = ids_of(37, seed=21)[0].tolist()
    a1 = served(eng, first, 6, session="s")
    before = eng.stats()
    second = first + a1 + ids_of(11, seed=22)[0].tolist()
    a2 = served(eng, second, 7, session="s")
    after = eng.stats()
    assert shortfall(params, first, a1) == 0.0
    assert shortfall(params, second, a2) == 0.0
    assert after["kv_prefix_hits"] == before["kv_prefix_hits"] + 1
    skipped = (after["kv_prefill_tokens_skipped"]
               - before["kv_prefill_tokens_skipped"])
    assert skipped == 37 // BLOCK * BLOCK          # the prompt's last edge
    assert (after["kv_state_reprefill_tokens"]
            > before["kv_state_reprefill_tokens"])


@pytest.fixture(scope="module")
def published(params):
    """The toy engine on the two paths the PUBLISHED widths take and the
    toy's do not, both rules patched on: every multi-token call of the
    GQA layer attends the live prefix in tiles, and a multi-lane call of
    a delta-rule layer runs its lanes one after another."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decode_attention, "prefix_tiled", lambda L, H, T: L > 1)
        patch.setattr(kda, "_LANE_BLOCK", 0)
        e = engine(params)
        yield e
        e.stop()


def test_the_engine_through_the_tiled_path(params, published):
    """The two-turn session again, on those paths."""
    first = ids_of(37, seed=21)[0].tolist()
    a1 = served(published, first, 6, session="s")
    second = first + a1 + ids_of(11, seed=22)[0].tolist()
    a2 = served(published, second, 7, session="s")
    assert shortfall(params, first, a1) == 0.0
    assert shortfall(params, second, a2) == 0.0
    stats = published.stats()
    assert stats["kv_prefix_hits"] == 1
    assert (stats["kv_prefill_rows_read"]
            <= stats["kv_prefill_rows_live"] + 128 * 8)


def test_the_default_ladder_serves_two_lanes_mapped(params, published,
                                                    monkeypatch):
    """The engine's own ladder, nothing overridden: two prompts admitted
    in ONE two-lane prefill, the delta-rule lanes one after another, are
    served the reference's tokens."""
    assert published.PREFILL_KS == (2, 1)
    lanes, fn = [], published._prefill_fn
    monkeypatch.setattr(published, "_prefill_fn",
                        lambda P, K: lanes.append(K) or fn(P, K))
    prompts = [ids_of(n, seed=n)[0].tolist() for n in (13, 11)]
    # with the engine mid-decode the two land in one tick's admission
    held = published.submit(np.asarray(ids_of(5)[0], np.int32), 24)
    futures = [published.submit(np.asarray(p, np.int32), 5) for p in prompts]
    for prompt, f in zip(prompts, futures):
        assert shortfall(params, prompt, f.result(300).tolist()) == 0.0
    held.result(300)
    assert 2 in lanes


def test_a_session_holds_one_snapshot_entry_where_they_are_short(eng):
    """With fewer free entries than pinned sessions a turn takes the
    entry of its session's OWN last snapshot for the deeper one it is
    about to write, before the free one; with every entry pinned anyone
    else is refused and counted; with entries to spare the old snapshot
    stays (``tests/test_serving_kv.py`` holds that)."""
    from edl_tpu.serving.kv_cache import PagedKVCache
    kv = PagedKVCache(eng._cache_shapes(1), BLOCK, 32, 4,
                      classes=eng._classes, n_snaps=4)
    held = {}
    for name, first in (("a", 1), ("b", 101)):
        _, _, tail = kv.commit(list(range(first, first + 2 * BLOCK)))
        held[name] = kv.snap_alloc(name)
        kv.snap_attach(kv.committed[0], held[name])
        kv.pin_session(name, tail)
    assert kv.snaps_used() == 2 and len(kv._snap_free) == 1
    assert kv.snap_alloc("a") == held["a"] and len(kv._snap_free) == 1
    assert kv.snap_alloc("c") > 0 and not kv._snap_free    # the free one
    assert kv.snap_alloc() == 0 and kv.snap_alloc("a") == 0
    assert kv.snap_skips == 2 and kv.snaps_used() == 1


@pytest.mark.parametrize("what", ["spec_k", "mesh"])
def test_refused_by_name(params, what):
    kw = ({"spec_k": 2, "draft_cfg": CFG, "draft_params": params}
          if what == "spec_k" else
          {"mesh": jax.make_mesh((2,), ("tp",))})
    with pytest.raises(ValueError, match="state-space configuration"):
        engine(params, **kw)


# -- the share -----------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(params):
    """The four shares of an expert layer (experts 0-3, 4-7, 8-11,
    12-15), the shared expert counted once, add up to the uncut
    reference's layer; and the program's held share is share 0."""
    whole = {**CONF, "n_routed_experts": ROUTER, "num_hidden_layers": 1}
    cfg = ref.transformer_config(whole, max_len=128, remat=False)
    moe = ref.init_params(cfg, 5, "float32")["layer_0"]["moe"]
    y = jax.random.normal(jax.random.key(2), (1, 19, 32))
    flat = y.reshape(19, 32)
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.moe_mlp(whole, moe, flat)
        shared = want - ref.held_experts(whole, moe, flat)[0]
        parts = []
        for lo in range(0, ROUTER, HELD):
            share = {**moe, **{k: moe[k][lo:lo + HELD]
                               for k in ("w_gate", "w_in", "w_out")}}
            parts.append(ref.held_experts(CONF, share, flat,
                                          (lo, lo + HELD))[0])
    close(sum(parts) + shared, want, 1e-5)
    first = {**moe, **{k: moe[k][:HELD] for k in ("w_gate", "w_in", "w_out")}}
    got = ref.program_experts(CFG, first, y, shared=False)[0]
    close(got, parts[0], 1e-4)
