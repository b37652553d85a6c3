"""Phi-4-mini-flash-reasoning's stack on the program's normal path against
the plain reference (``benchmarks/archs/phi4flash.py``: float32, the
recurrence one token at a time, differential attention as two softmaxes
over striped heads, every layer at every position, no cache, no kernels;
nothing of ``edl_tpu`` in it), at a toy size on the CPU: 8 layers (Mamba-1
/ window 8 x 2, Mamba-1 that emits the memory, one full layer, a gated
memory unit, a cross layer), hidden 32, 8 query and 4 key / value heads of
4, inner 64 with a state of 4 and a dt of rank 4, vocabulary 64.  The
system computes in float32 here.

TOLERANCE: 1e-4 relative (of the largest reference magnitude), as
``test_granite_moe_hybrid.py`` has it.  Measured here: 1e-7 to 2e-6.  Each
deliberately wrong program reads 1e-2 or more.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models import transformer
from edl_tpu.models.transformer import (Block, TransformerConfig,
                                        TransformerLM, param_count)
from edl_tpu.ops import mamba1
from edl_tpu.serving import cache_layout
from edl_tpu.serving.engine import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 1e-4
BLOCK, WINDOW, VOCAB = 4, 8, 64
CONF = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 32,
    "intermediate_size": 48, "layer_norm_eps": 1e-5,
    "max_position_embeddings": 4096, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 8,
    "num_hidden_layers": 8, "num_key_value_heads": 4, "resid_pdrop": 0,
    "sliding_window": WINDOW, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": VOCAB,
    "assumed_sizes": {"mamba_d_state": 4, "mamba_d_conv": 4,
                      "mamba_expand": 2, "mamba_dt_rank": 4},
    "run": {"compute_dtype": "float32", "param_dtype": "float32",
            "ssm_state_dtype": "float32", "prefill_chunk": 16},
}


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


arch = _load("phi4flash_arch",
             os.path.join(ROOT, "benchmarks", "archs", "phi4flash.py"))
CFG = arch.transformer_config(CONF, max_len=96, remat=False,
                              attention_impl="dense")


def ids_of(length, seed=3, batch=1):
    return jax.random.randint(jax.random.key(seed), (batch, length), 1, VOCAB)


@pytest.fixture(scope="module")
def params():
    return arch.init_params(CFG, 7, "float32")


def error(got, want):
    want = np.asarray(want)
    return np.abs(np.asarray(got) - want).max() / np.abs(want).max()


def close(got, want, rtol=RTOL):
    assert np.shape(got) == np.shape(want)
    err = error(got, want)
    assert err <= rtol, f"relative error {err:.2e} over {rtol:.0e}"


def engine(params, cfg=CFG, **kw):
    kw = dict(dict(slots=3, max_len=96, temperature=0.0, steps_per_sync=4,
                   kv_block=BLOCK, kv_pool_blocks=96, prefill_chunk=16,
                   prefill_buckets=(8, 16, 32)), **kw)
    return ContinuousBatcher(cfg, params, **kw)


def greedy(params, prompt, out):
    """Whether ``out`` is the reference's own greedy continuation of
    ``prompt``: ONE full pass over prompt + answer, teacher-forced; by
    induction over the answer's tokens the argmax at every answer
    position is the served token."""
    ids = jnp.asarray([list(prompt) + list(out)])[:, :-1]
    at = np.asarray(arch.logits(CONF, params, ids))[0, len(prompt) - 1:]
    return at.argmax(-1).tolist() == list(out)


def served(eng, prompt, n, **kw):
    return eng.submit(np.asarray(prompt, np.int32), n, **kw).result(
        300).tolist()


# -- the stack and its mixers ---------------------------------------------------

def test_the_plan_is_the_published_one():
    assert arch.layer_kinds(CONF) == (
        "mamba", "window", "mamba", "window", "mamba", "full", "gmu", "cross")
    assert CFG.layer_attn == ("mamba1", "window", "mamba1", "window",
                              "mamba1", "global", "gmu", "cross")
    assert CFG.tail_start == 6 and CFG.shares
    assert not CFG.rope_global and not CFG.rope_window
    plain = TransformerConfig(num_layers=2)
    assert plain.tail_start == 2 and not plain.shares
    with pytest.raises(ValueError, match="no Mamba-1 layer below"):
        dataclasses.replace(CFG, layer_attn=("gmu",) + CFG.layer_attn[1:])
    with pytest.raises(ValueError, match="no global layer below"):
        dataclasses.replace(CFG, layer_attn=("cross",) + CFG.layer_attn[1:])
    with pytest.raises(ValueError, match="maps no key"):
        arch.transformer_config(dict(CONF, rope_theta=1e4), max_len=96)


@pytest.mark.parametrize("length", [5, 16, 21, 40])
def test_full_forward_equals_the_reference(params, length):
    """The non-decode ``TransformerLM`` (what a trainer would call)."""
    ids = ids_of(length, batch=2)
    got = TransformerLM(CFG).apply({"params": params}, ids)
    close(got, arch.logits(CONF, params, ids))


def test_param_count_is_the_trees(params):
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == param_count(CFG) == arch.param_count(CONF)


@pytest.fixture(scope="module")
def agreement(params):
    ids = ids_of(44, seed=5)
    ref = arch.reference(CONF, params, ids)
    return arch.block_agreement(CONF, params, ids, ref, cfg=CFG)


@pytest.mark.parametrize("key", [
    "mixer_error", "window_error", "attention_error", "gmu_error",
    "state_error",
    "logit_error_sigma", "cache_error_sigma", "cross_step_error"])
def test_each_mixer_and_the_stack_agree_with_the_reference(agreement, key):
    """``block_agreement`` (what the benchmark's ``correct`` rests on): a
    Mamba-1 mixer alone, a differential attention layer alone (window,
    full, cross), a GMU alone, the carried state after a chunk and
    one-token updates, the stack's logits, and the stack through its
    cache: chunks without the tail, the last-position cut, one-token
    steps past three windows."""
    assert agreement[key].size and agreement[key].max() <= RTOL


@pytest.mark.parametrize("length", [9, 16, 33])
def test_a_prefill_with_the_cut_gives_the_full_passes_last_logits(
        params, length):
    """Layers 6 and 7 at the lanes' last real rows alone (two lanes of
    unequal length, padded) against every layer at every position."""
    model = TransformerLM(dataclasses.replace(CFG, decode=True))
    lens = jnp.asarray([length, length - 4])
    ids = ids_of(length, batch=2)
    mask = jnp.arange(length)[None] < lens[:, None]
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init(jax.random.key(0), ids[:, :1]))["cache"])
    got, mut = model.apply({"params": params, "cache": cache}, ids,
                           token_mask=mask, last_at=lens - 1,
                           mutable=["cache", "intermediates"])
    assert got.shape == (2, 1, VOCAB)
    assert set(mut["cache"]) == {f"layer_{i}" for i in range(6)}
    for lane in range(2):
        n = int(lens[lane])
        want = arch.logits(CONF, params, ids[lane:lane + 1, :n])[0, -1]
        close(got[lane, 0], want)
    # and a chunk that samples nothing leaves the tail out altogether
    hidden, _ = model.apply({"params": params, "cache": cache}, ids,
                            tail=False, mutable=["cache", "intermediates"])
    assert hidden.shape == (2, length, CFG.embed_dim)


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_prefill_carries_state_and_rows(params, chunk):
    ids = ids_of(50, seed=9)
    got = arch.program_cached(CFG, params, ids, chunk, 12)
    close(got, arch.logits(CONF, params, ids)[0, -13:-1])


# -- the engine -------------------------------------------------------------------

def test_decode_through_the_cache_past_two_windows(params):
    """A prompt of 21 (a chunk of 16 and a bucket of 8) and 26 tokens
    decoded: 47 positions, more than five windows of 8; the cross layer
    reads the full layer's slab, the GMU the step's own memory."""
    prompt = np.asarray(ids_of(21, seed=4))[0].tolist()
    eng = engine(params)
    try:
        assert greedy(params, prompt, served(eng, prompt, 26))
        stats = eng.stats()
        # 16 + 5 real tokens below the tail, the tail at one row
        assert stats["prefill_layer_visits"] == 21 * 6 + 2
        assert stats["prefill_layer_visits_cut"] == 21 * 8 - (21 * 6 + 2)
        assert stats["borrowed_kv_tokens_prefill"] == 21
        assert stats["borrowed_kv_tokens_live"] == (
            stats["decode_kv_tokens_live"])       # one borrowing layer
        assert stats["ssm_state_steps"] % 3 == 0  # three Mamba-1 layers
    finally:
        eng.stop()


def test_a_batch_of_unequal_prompts_is_served_as_if_each_alone(params):
    prompts = [np.asarray(ids_of(n, seed=20 + n))[0].tolist()
               for n in (5, 7, 12)]
    eng = engine(params)
    try:
        futures = [eng.submit(np.asarray(p, np.int32), 9) for p in prompts]
        for p, f in zip(prompts, futures):
            assert greedy(params, p, f.result(300).tolist())
    finally:
        eng.stop()


def test_a_pool_hit_resumes_from_the_rings_and_states_snapshots(params):
    """The first prompt (22 tokens) leaves its rings' and states'
    snapshot at its last block edge (20) and the full layer's blocks in
    the pool; a prompt that extends it gathers ONE layer's rows for
    both the full layer and the cross layer, and prefills the rest."""
    first = np.asarray(ids_of(22, seed=6))[0].tolist()
    eng = engine(params)
    try:
        assert greedy(params, first, served(eng, first, 7))
        s0 = eng.stats()
        longer = first + np.asarray(ids_of(9, seed=7))[0].tolist()
        out = served(eng, longer, 6)
        s1 = eng.stats()
        assert greedy(params, longer, out)
        assert s1["kv_prefix_hits"] - s0["kv_prefix_hits"] == 1
        assert (s1["kv_prefill_tokens_skipped"]
                - s0["kv_prefill_tokens_skipped"]) == 20
    finally:
        eng.stop()


def test_session_export_and_import_with_a_borrowing_layer(params):
    prompt = np.asarray(ids_of(27, seed=13))[0].tolist()
    a = engine(params)
    answer = served(a, prompt, 6, session="s")
    assert a.drain(60)
    exported = a.export_sessions()
    assert len(exported) == 1
    session, tokens, meta, blob = exported[0]
    # the layers that own something are on the wire, the others are not
    assert meta["state_layers"] and meta["ring_layers"]
    named = {name for key in ("state_layers", "ring_layers")
             for name in meta[key]}
    assert not named & {"layer_6", "layer_7"}
    b = engine(params)
    try:
        assert b.import_session(session, tokens, meta, blob) == len(
            tokens) // BLOCK
        nxt = prompt + answer + [9, 8, 7]
        s0 = b.stats()
        out = served(b, nxt, 5, session="s")
        s1 = b.stats()
        assert greedy(params, nxt, out)
        assert (s1["kv_prefill_tokens_skipped"]
                - s0["kv_prefill_tokens_skipped"]) == len(tokens)
    finally:
        b.stop()


def test_cache_classes_gives_owning_empty_and_borrowing_rows():
    dcfg = dataclasses.replace(CFG, decode=True)
    classes = cache_layout.cache_classes(dcfg)
    kinds = [classes[f"layer_{i}"].kind for i in range(8)]
    assert kinds == ["state", "window", "state", "window", "state",
                     "global", "empty", "borrowed"]
    assert isinstance(classes["layer_0"], cache_layout.Mamba1State)
    assert classes["layer_0"] is classes["layer_4"]
    assert classes["layer_7"].lender == "layer_5"
    assert classes["layer_6"].buffers({}) == {} == classes[
        "layer_7"].buffers({})
    assert classes["layer_7"].no_rewind and classes["layer_7"].no_shard
    # the cache tree holds the owners alone; a slot's one growing cache
    # is layer 5's
    model = TransformerLM(dcfg)
    ids = jnp.zeros((1, 1), jnp.int32)
    cache = jax.eval_shape(lambda: model.init(jax.random.key(0), ids))["cache"]
    assert set(cache) == {f"layer_{i}" for i in range(6)}
    assert cache["layer_5"]["cached_key"].shape == (1, 2, 8, 96)
    assert cache["layer_0"]["ssm"]["ssm_state"].shape == (1, 4, 64)


@pytest.mark.parametrize("what", ["spec_k", "mesh"])
def test_what_cannot_serve_the_stack_refuses_at_construction(params, what):
    if what == "spec_k":
        with pytest.raises(ValueError, match="does not serve a"):
            engine(params, spec_k=2, draft_cfg=CFG, draft_params=params)
    else:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
        with pytest.raises(ValueError, match="does not serve a"):
            engine(params, mesh=mesh)


def test_the_programs_carry_the_scopes_the_registry_states(params):
    """``model_counters.SCOPES``: a one-token step names ``mamba1/step``,
    a multi-token call ``mamba1/scan``, both the rest."""
    from edl_tpu.serving.model_counters import SCOPES
    model = TransformerLM(dataclasses.replace(CFG, decode=True))
    cache = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((2, 1), jnp.int32)))["cache"]

    def text(width):
        ids = jnp.zeros((2, width), jnp.int32)
        return jax.jit(lambda p, c: model.apply(
            {"params": p, "cache": c}, ids, mutable=["cache"])).lower(
                params, cache).as_text(debug_info=True)

    step, chunk = text(1), text(8)
    for kind in ("mamba1", "gmu", "cross"):
        for scope in SCOPES[kind]:
            where = {"mamba1/step": (step,), "mamba1/scan": (chunk,)}.get(
                scope, (step, chunk))
            assert all(scope in t for t in where), scope


# -- the ops ----------------------------------------------------------------------

def _step_inputs(B=5, N=4, Di=128, seed=0):
    ks = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(ks[0], (B, N, Di)),
            jax.random.normal(ks[1], (B, Di)),
            jax.nn.softplus(jax.random.normal(ks[2], (B, Di))),
            -jnp.exp(jax.random.normal(ks[3], (N, Di))),
            jax.random.normal(ks[4], (B, N)), jax.random.normal(ks[5], (B, N)))


@pytest.mark.parametrize("live", [(1, 1, 1, 1, 1), (0, 1, 0, 1, 1),
                                  (0, 0, 1, 0, 0), (0, 0, 0, 0, 0)])
def test_the_step_kernel_is_the_reference_and_skips_free_slots(live):
    args = _step_inputs()
    live = jnp.asarray(live, bool)
    want_y, want_s = mamba1.mamba1_step_reference(*args, live)
    got_y, got_s = mamba1.mamba1_step(*args, live, interpret=True)
    close(got_y, want_y, 1e-6) if live.any() else None
    np.testing.assert_allclose(got_s, want_s, rtol=1e-6, atol=1e-6)
    assert float(mamba1.slots_fetched(live)) == max(1, int(live.sum()))


def test_the_scan_is_the_steps_with_lengths_and_a_snapshot():
    state, _, _, A, _, _ = _step_inputs(B=2)
    ks = jax.random.split(jax.random.key(1), 4)
    L = 11
    x = jax.random.normal(ks[0], (2, L, 128))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, L, 128)))
    Bm = jax.random.normal(ks[2], (2, L, 4))
    Cm = jax.random.normal(ks[3], (2, L, 4))
    lengths, at = jnp.asarray([11, 7]), jnp.asarray([4, 7])
    ys, final, snap = mamba1.selective_scan(x, dt, A, Bm, Cm, state,
                                            lengths=lengths, snap_at=at)
    for lane in range(2):
        s = state[lane:lane + 1]
        for t in range(int(lengths[lane])):
            y, s = mamba1.mamba1_step_reference(
                s, x[lane:lane + 1, t], dt[lane:lane + 1, t], A,
                Bm[lane:lane + 1, t], Cm[lane:lane + 1, t])
            close(ys[lane, t], y[0], 1e-5)
            if t + 1 == int(at[lane]):
                close(snap[lane], s[0], 1e-5)
        close(final[lane], s[0], 1e-5)


# -- deliberately wrong programs --------------------------------------------------

def _patched(monkeypatch, name):
    block, gmu = Block, transformer.GatedMemoryUnit
    lam, cross, unit = block._lambda, block._cross_attention, gmu.__call__
    if name == "lam0":
        monkeypatch.setattr(block, "_lambda",
                            lambda self, w: (0.0, lam(self, w)[1]))
    elif name == "rows_short":
        monkeypatch.setattr(
            block, "_cross_attention",
            lambda self, q, pos, mask, lent: cross(self, q, pos - 1, mask,
                                                   lent))
    elif name == "stale_memory":
        monkeypatch.setattr(
            gmu, "__call__",
            lambda self, u, m: unit(self, u, jnp.roll(m, 1, axis=1)))


WRONG = {"lam0": ("attention_error", {}),
         "stale_memory": ("gmu_error", {}),
         "rows_short": ("cross_step_error", {}),
         "window7": ("window_error", {"attn_window": WINDOW - 1}),
         "state_bf16": ("state_error", {"ssm_state_dtype": jnp.bfloat16}),
         "rmsnorm": ("logit_error_sigma", {"norm": "rms"})}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_wrong_variant_breaks_the_tolerance(params, name, monkeypatch):
    """What ``benchmarks/tests/chip_phi4flash_variants.py`` reads on the
    chip, at toy width: each wrong program is far outside the tolerance
    by the number named for it."""
    key, change = WRONG[name]
    _patched(monkeypatch, name)
    ids = ids_of(44, seed=5)
    ref = arch.reference(CONF, params, ids)
    got = arch.block_agreement(CONF, params, ids, ref,
                               cfg=dataclasses.replace(CFG, **change))
    # (rows one short leave position 0 nothing to read: a NaN on the
    # einsum path, which no limit admits either)
    assert not np.median(got[key]) <= (1e-3 if name == "state_bf16"
                                       else 1e-2)


# -- what the new fields leave alone ----------------------------------------------

def _tree(shapes):
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            [list(leaf.shape), str(leaf.dtype)]
            for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}


@pytest.mark.parametrize("name,module,own", [
    ("toy-serve", "model", None),
    ("toy-exaone", "archs/exaone_moe", "exaone_moe"),
    ("toy-granite", "archs/granite_moe_hybrid", "granite_moe_hybrid")])
def test_existing_configurations_keep_their_trees(name, module, own):
    """Every new field is off by default: the Mistral, K-EXAONE and
    granite toy decode models build the parameter and cache trees
    (names, shapes, dtypes) recorded from the parent commit
    (``tests/data/toy_trees.json``)."""
    bench = os.path.join(ROOT, "benchmarks")
    with open(os.path.join(bench, "tests", "toy", "configs",
                           f"{name}.json")) as f:
        conf = json.load(f)
    mod = _load(f"toy_{name}", os.path.join(bench, f"{module}.py"))
    cfg = mod.transformer_config(conf, max_len=conf["run"]["max_len"],
                                 remat=False)
    model = TransformerLM(dataclasses.replace(cfg, decode=True,
                                              attention_impl="dense"))
    ids = jnp.zeros((2, 1), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), ids,
                                               positions=ids))
    with open(os.path.join(ROOT, "tests", "data", "toy_trees.json")) as f:
        want = json.load(f)[name]
    assert _tree(shapes["params"]) == want["params"]
    assert _tree(shapes["cache"]) == want["cache"]


# -- the benchmark's files -----------------------------------------------------------

BENCH = os.path.join(ROOT, "benchmarks")
CELL, CONFIG = "serve-sambay-reason-open", "phi-4-mini-flash-reasoning-serve"


def conf_of():
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def test_the_file_keeps_every_published_number():
    """The catalog's copy of config.json, where this sandbox has it:
    every number under the same key, nothing reduced."""
    conf = conf_of()
    assert conf["reduced"] == [] and conf["reduced_from"] == {}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "Phi-4-mini-flash-reasoning")
    assert conf["source"] == entry["source_url"]
    assert {k for k, v in entry["config"].items() if conf.get(k) != v} == set()


def test_arch_maps_the_file_and_its_counts_are_the_files():
    conf = conf_of()
    cfg = arch.transformer_config(conf, max_len=20480)
    assert (cfg.embed_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.vocab_size) == (2560, 40, 20, 64, 10240, 200064)
    assert (cfg.m1_inner, cfg.m1_state, cfg.m1_conv, cfg.m1_dt_rank) == (
        5120, 16, 4, 160)
    assert cfg.attn_window == 512 and cfg.norm == "layer" and cfg.diff_attn
    assert cfg.layer_attn[:4] == ("mamba1", "window", "mamba1", "window")
    assert cfg.layer_attn[16:20] == ("mamba1", "global", "gmu", "cross")
    assert cfg.tail_start == 18 and cfg.num_layers == 32
    assert (arch.param_count(conf) == conf["memory"]["parameters"]
            == 3_852_562_944 == param_count(cfg))
    assert arch.mamba_params(conf) == 41_241_600
    assert arch.attention_params(conf) == 19_668_864
    assert arch.attention_params(conf, cross=True) == 13_112_704
    assert arch.gmu_params(conf) == 26_214_400
    assert (arch.mamba_layers(conf), arch.window_layers(conf),
            arch.kv_readers(conf)) == (9, 8, 8)
    assert arch.kv_bytes_per_token(conf) == 5120
    assert arch.state_bytes_per_slot(conf) == 9 * (5120 * 16 * 4
                                                   + 3 * 5120 * 2)
    flops, nbytes = arch.ssm_step_min(conf, 1.0)
    assert flops == 6 * 5120 * 16 and 8 * 5120 * 16 < nbytes < 9 * 5120 * 16


# a 45 s window of the cell: 700 ticks x 4 token steps, 20 of 32 live
COUNTERS = {
    "window_s": 45.0, "steps_per_sync": 4,
    "prefill_layer_visits": 18 * 12_000 + 14 * 50,
    "prefill_layer_visits_cut": 14 * 12_000 - 14 * 50,
    "trace_span_counters": {
        "ssm_state_steps": 9 * 5_000, "decode_kv_tokens_live": 5_000_000,
        "decode_kv_tokens_window_need": 2_400_000,
        "borrowed_kv_tokens_prefill": 40_000,
        "prefill_layer_visits": 18 * 1_000 + 14 * 5,
        "prefill_layer_visits_cut": 14 * 1_000 - 14 * 5},
}
TRACE = {"window_s": 4.0, "busy_s": 3.8,
         "ops": {"mamba1_step.7_f32_32_1_5120_": 0.09,
                 "decode_attend.3_bf16_32_10_8_128_": 0.6,
                 "window_attend.1_bf16_32_10_8_128_": 0.2},
         "modules": {"jit__step_impl": {"count": 62, "total_s": 3.5}}}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["mamba1_step_roofline", "shared_kv_attend_roofline",
       "yoco_prefill_skip_share", "sambay_step_mfu"]


def ctx(counters, trace):
    return {"counters": counters, "trace": trace, "peak": PEAK,
            "conf": conf_of()}


def reader(name):
    import sys
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(f"layer_metrics.{name}").read


def test_new_readers_on_a_recorded_counter_set():
    c, conf = ctx(dict(COUNTERS), TRACE), conf_of()
    assert reader("yoco_prefill_skip_share")(c) == pytest.approx(
        100.0 * (14 * 12_000 - 700) / (32 * 12_000))
    flops, nbytes = arch.ssm_step_min(conf, 45_000)
    assert nbytes / 819e9 > flops / 197e12              # memory bound
    assert reader("mamba1_step_roofline")(c) == pytest.approx(
        100.0 * (nbytes / 819e9) / 0.09)
    flops, nbytes = arch.shared_kv_attend_min(conf, 8 * 5_000_000 + 40_000)
    assert reader("shared_kv_attend_roofline")(c) == pytest.approx(
        100.0 * (nbytes / 819e9) / 0.6)
    want = (arch.step_flops(conf, 5_000, 8 * 5_000_000, 8 * 2_400_000)
            + arch.prefill_flops(conf, 1_000, 5, 0.0, 0.0))
    assert reader("sambay_step_mfu")(c) == pytest.approx(
        100.0 * want / 197e12 / 3.8)
    for name in NEW:
        assert 0 < reader(name)(c) < 100.0


@pytest.mark.parametrize("name", NEW)
def test_new_readers_say_nothing_where_there_is_nothing(name):
    """The parent's engine has none of these counters, and an untraced
    run has no trace: the reader returns None and never raises."""
    old = {"window_s": 45.0, "steps_per_sync": 4, "ssm_state_steps": 7,
           "trace_span_counters": {"ssm_state_steps": 3,
                                   "decode_kv_tokens_live": 9}}
    if name != "mamba1_step_roofline":      # the kernel is new, too
        assert reader(name)(ctx(old, TRACE)) is None
    assert reader(name)(ctx({"window_s": 45.0}, TRACE)) is None
    if name != "yoco_prefill_skip_share":
        assert reader(name)(ctx(dict(COUNTERS), None)) is None
        untapped = {k: v for k, v in COUNTERS.items()
                    if k != "trace_span_counters"}
        assert reader(name)(ctx(untapped, TRACE)) is None
    if "roofline" in name:
        no_kernel = dict(TRACE, ops={"window_attend.1": 0.5})
        assert reader(name)(ctx(dict(COUNTERS), no_kernel)) is None


def test_the_spec_names_the_cell_and_its_traffic_fits_the_engine():
    import sys
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from generators import open_trace
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reason-cot-open", 1)
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and entry["file"].endswith(f"{CONFIG}.json")
    listed = {m["name"] for m in spec["per_layer"]
              if CELL in m.get("workloads", [])}
    # ``mamba1_step_roofline`` has its reader and is NOT listed: on the
    # chip it read 125-138% (PERF.md section 7), and a share over 100
    # refuses every PR that reports it
    assert set(NEW) - {"mamba1_step_roofline"} <= listed
    assert "mamba1_step_roofline" not in listed
    for name in listed:
        reader(name)
    with open(os.path.join(BENCH, "traffic", "reason-cot-open.json")) as f:
        traffic = json.load(f)
    assert traffic["generator"] == "open_trace" and traffic["loop"] == "open"
    assert traffic["prompt_tokens"]["parts"] == [
        {"share": 0.95, "dist": "lognormal", "median": 256, "sigma": 0.8,
         "min": 32, "max": 2048},
        {"share": 0.05, "dist": "lognormal", "median": 8192, "sigma": 0.4,
         "min": 4096, "max": 16384}]
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 256,
        "max": 2048}
    assert (traffic["warm_seconds"], traffic["drain_seconds"]) == (20.0, 30.0)
    assert traffic["probe_tokens"] == 1100 and not traffic["shared_prefix"]
    conf, seconds = conf_of(), float(spec["run_seconds"])
    shapes = open_trace.shapes(traffic, seconds, conf["run"]["kv_block"])
    assert shapes["max_total"] <= conf["run"]["max_len"] == 20480
    plan = open_trace.schedule(traffic, 2147483659, seconds, conf["vocab_size"])
    assert plan["offered"]["requests"] == round(traffic["rate_per_s"]
                                                * seconds)
