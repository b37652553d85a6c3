"""Generation by diffusion over blocks on the program's normal path
(``TransformerConfig.block_length``, the engine's pass program) against
the plain reference (``benchmarks/archs/sdar_moe.py``: float32, no
cache, the whole sequence recomputed every pass), at a toy size on the
CPU: 2 layers, hidden 64, 4 heads of 16 over 2 KV heads with per-head
q/k norms, 8 softmax experts top-2 of width 32 renormalised, vocabulary
128, the mask id its last row.  The system computes in float32 here so
that it routes, and ranks confidences, as the reference does.

TOLERANCE on logits: 2e-4 of the largest reference magnitude (measured
1e-6 to 2e-5: float32 sums in another order).  Tokens are compared
exactly: a near-tie of float32 logits has not been seen at this size.
"""

import dataclasses
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models.transformer import (TransformerConfig, TransformerLM,
                                        param_count)
from edl_tpu.ops import decode_attention
from edl_tpu.serving.engine import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
RTOL = 2e-4
MASK = 127


def _bench(name):
    """A module of the benchmark (``archs.sdar_moe``,
    ``runners.serve_blockdiff``), as its own tests import it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module(name)


arch = _bench("archs.sdar_moe")


def conf_of(L=4, steps=0, remasking="static", threshold=0.9):
    return {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
        "max_position_embeddings": 256, "max_window_layers": 2,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 32, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
        "num_hidden_layers": 2, "num_key_value_heads": 2,
        "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 128,
        "run": {"compute_dtype": "float32", "param_dtype": "float32",
                "block_length": L, "denoising_steps": steps or L,
                "remasking": remasking, "mask_token_id": MASK,
                "confidence_threshold": threshold}}


_made = {}


def model(L=4, **kw):
    """``(conf, cfg, params)`` of the toy at block length ``L``; one set
    of weights whatever the generation settings."""
    conf = conf_of(L, **kw)
    cfg = arch.transformer_config(conf, max_len=128, remat=False)
    if "params" not in _made:
        _made["params"] = arch.init_params(cfg, 3, "float32",
                                           split_layers=False)
    return conf, cfg, _made["params"]


def engine(conf, cfg, params, **kw):
    kw = {"slots": 3, "max_len": 128, "temperature": 0.0,
          "steps_per_sync": 5, "kv_block": 8, "prefill_chunk": 16,
          "prefill_buckets": (8, 16, 32), **kw}
    return ContinuousBatcher(cfg, params, **kw)


def prompt_of(n, seed=0):
    return np.asarray(jax.random.randint(jax.random.key(100 * seed + n),
                                         (n,), 1, MASK))


def close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= RTOL * np.abs(want).max(), \
        np.abs(got - want).max() / np.abs(want).max()


# -- the forward under M -----------------------------------------------------
@pytest.mark.parametrize("L", [1, 2, 4])
def test_forward_under_the_block_causal_mask_equals_the_reference(L):
    conf, cfg, params = model(L)
    ids = jax.random.randint(jax.random.key(1), (2, 24), 1, MASK)
    got = TransformerLM(dataclasses.replace(
        cfg, attention_impl="dense")).apply({"params": params}, ids)
    close(got, arch.logits(conf, params, ids))
    if L == 1:
        # block length 1 IS causal: today's forward, to the bit
        causal = TransformerLM(dataclasses.replace(
            cfg, attention_impl="dense", block_length=0)).apply(
            {"params": params}, ids)
        assert (np.asarray(got) == np.asarray(causal)).all()
    else:
        # and a later row of a block moves an earlier row's logits
        flipped = ids.at[:, L - 1].set(5)
        moved = arch.logits(conf, params, flipped) - arch.logits(
            conf, params, ids)
        assert np.abs(np.asarray(moved[:, 0])).max() > 1e-3


def test_block_length_zero_builds_the_modules_it_always_did():
    cfg = TransformerConfig(vocab_size=64, num_layers=1, embed_dim=32,
                            num_heads=2, mlp_dim=64, max_len=32)
    assert cfg.block_length == 0 and cfg.pass_tokens == 1
    # a pass carries a commit half and an open half
    assert dataclasses.replace(cfg, decode=True, decode_scatter=True,
                               block_length=4).pass_tokens == 8


# -- through the cache -------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    """One engine at the cell's settings (L = 4, 4 steps, static) and
    the runner's check (a) beside it."""
    conf, cfg, params = model()
    eng = engine(conf, cfg, params)
    yield conf, cfg, params, eng
    eng.stop()


def test_prefill_commits_and_a_half_masked_pass_equal_the_full_forward(
        served):
    """The engine's own programs (bucketed prefill, the chunk lane's
    start / mid / last, the pass program's forward) on a one-lane cache:
    ``runners/serve_blockdiff.logit_check``, the cell's check (a)."""
    conf, cfg, params, eng = served
    runner = _bench("runners.serve_blockdiff")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, MASK, 16 + 17 + 2 * 4).tolist(),   # chunked
               rng.integers(1, MASK, 5 * 4 + 3).tolist()]         # a tail
    out = runner.logit_check(eng, arch, conf, params, 7, prompts=prompts)
    assert out["logit_error_sigma"].shape == (24,)
    assert out["logit_error_sigma"].max() < 1e-4
    assert np.median(out["expert_error"]) < 1e-4
    # a commit that does not write leaves the denoise pass's K/V: seen
    wrong = runner.logit_check(eng, arch, conf, params, 7, prompts=prompts,
                               commit_writes=False)
    assert wrong["logit_error_sigma"].max() > 0.01


@pytest.mark.parametrize("chunks_alone", [False, True],
                         ids=["every_program", "chunk_programs_alone"])
def test_a_causal_prefill_is_seen_by_the_logit_check(chunks_alone):
    """A causal mask in place of ``M``: in every multi-token program, or
    in the chunk lane's mid and last programs alone (a program family
    captures the model where it is built): the prompt that goes through
    them reads it, the one that does not reads nothing."""
    conf, cfg, params = model()
    eng = engine(conf, cfg, params)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, MASK, 16 + 17 + 2 * 4).tolist(),   # chunked
               rng.integers(1, MASK, 11).tolist()]
    try:
        right = eng._model
        eng._model = TransformerLM(dataclasses.replace(
            eng._dcfg, block_length=0))
        if chunks_alone:
            eng._chunk_mid_fn(16)
            for b in eng._buckets:
                eng._chunk_final_fn(b)
            eng._model = right
        runner = _bench("runners.serve_blockdiff")
        out = runner.logit_check(eng, arch, conf, params, 7, prompts=prompts)
        chunked, plain = out["logit_error_by_prompt"]
        assert chunked > 0.01
        assert (plain < 1e-4) if chunks_alone else (plain > 0.01)
    finally:
        eng.stop()


GEN = {"static4": dict(steps=4), "static2": dict(steps=2),
       "static1": dict(steps=1),
       "dynamic": dict(steps=4, remasking="dynamic", threshold=0.02)}
_engines = {}


@pytest.fixture(scope="module")
def engines():
    yield _engines
    for eng in _engines.values():
        eng.stop()


# P % L in {0, 1, 3}, one prompt shorter than a block, and an answer
# that is no multiple of L
@pytest.mark.parametrize("P,N", [(8, 8), (9, 6), (11, 12), (3, 5)])
@pytest.mark.parametrize("name", sorted(GEN))
def test_engine_tokens_equal_block_diffusion_generate(engines, name, P, N):
    conf, cfg, params = model(**GEN[name])
    if name not in engines:
        engines[name] = engine(conf, cfg, params)
    eng, prompt = engines[name], prompt_of(P)
    before, lane_steps = eng.stats(), eng._active_lane_steps
    got = eng.generate(prompt, N, timeout=300).tolist()
    after = eng.stats()
    trace = []
    want = arch.block_diffusion_generate(conf, params, prompt.tolist(), N,
                                         trace=trace, **arch.generation(conf))
    assert len(got) == N and got == want
    d = {k: after[k] - before[k] for k in after if k.startswith("blockdiff_")}
    blocks = -(-(P % 4 + N) // 4)
    assert d["blockdiff_blocks_committed"] == blocks
    assert d["blockdiff_tokens_delivered"] == N
    assert d["blockdiff_given_tokens"] == P % 4
    assert d["blockdiff_tokens_unmasked"] == 4 * blocks - P % 4
    # a block through the model a reference pass or a commit, whichever
    # forward carried it; every commit but the budget's last rode the
    # next block's first pass, so the live (slot, pass) pairs are the
    # reference's passes and ONE more
    assert d["blockdiff_slot_passes"] == len(trace) + blocks
    assert d["blockdiff_commits_fused"] == blocks - 1
    pairs = d["blockdiff_slot_passes"] - d["blockdiff_commits_fused"]
    assert pairs == len(trace) + 1 == eng._active_lane_steps - lane_steps
    if name == "static4" and P % 4 == 0:
        assert pairs == 4 * blocks + 1
    if name == "static1":
        # a pass a block, and the first block's own
        assert pairs == blocks + 1
    # a pass yields 0 or L tokens a slot: the gap is the time after the
    # first commit over the tokens that came after it, and a lane step
    # is a (slot, pass) pair
    first = min(N, 4 - P % 4)
    assert after["decode_tokens"] - before["decode_tokens"] == N - first
    # the commit halves' rows are routed with the open halves'
    assert after["moe_tokens"] - before["moe_tokens"] == (
        P // 4 * 4 + 4 * d["blockdiff_slot_passes"])
    assert after["moe_assignments"] - before["moe_assignments"] == 2 * 2 * (
        after["moe_tokens"] - before["moe_tokens"])


# three slots live at once, at different rows and different places in
# their blocks: prompts of P % L = 0, 1, 3 (so the first blocks take 4, 3
# and 1 denoise passes and the slots commit in different passes ever
# after), answers of different lengths (one no multiple of L, one that
# ends while the others go on), and a fourth request that takes the slot
# the first to finish frees, beside two slots in mid-answer
TOGETHER = [(8, 22), (9, 12), (15, 17), (6, 9)]


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["einsum", "interpret"])
def test_slots_live_together_serve_what_each_would_alone(monkeypatch,
                                                         kernels):
    conf, cfg, params = model()
    if kernels:
        monkeypatch.setattr(decode_attention, "block_applies",
                            lambda L, mesh, max_len, dtype: L == 4)
    eng = engine(conf, cfg, params, steps_per_sync=3)
    prompts = [prompt_of(P, seed=7 + i) for i, (P, _) in enumerate(TOGETHER)]
    try:
        eng.pass_log = {}
        futs = [eng.submit(p, N) for p, (_, N) in zip(prompts, TOGETHER)]
        got = [f.result(timeout=600).tolist() for f in futs]
        logs = [eng.pass_log[f] for f in futs]
    finally:
        eng.stop()
    for prompt, (P, N), out, log in zip(prompts, TOGETHER, got, logs):
        want = arch.block_diffusion_generate(
            conf, params, prompt.tolist(), N, **arch.generation(conf))
        assert out == want, (P, N)
        # the log holds the request's every pass, in order: the first
        # block as admission made it, a commit a block
        assert log[0]["masked"] == [i >= P % 4 for i in range(4)]
        assert sum(e["commit"] for e in log) == -(-(P % 4 + N) // 4)
    # the first three were live together, and the fourth beside two
    assert max(e["live"] for e in logs[0]) == 3
    assert max(e["live"] for e in logs[3]) >= 2
    # and the slots keep step: every commit, of every request, in a pass
    # of the engine's that is a multiple of 4 (``_start_wait``), so that
    # three passes of four carry no commit half
    commits = {e["at"] for log in logs for e in log if e["commit"]}
    assert commits and all(at % 4 == 0 for at in commits)


@pytest.mark.parametrize("mixed", [False, True], ids=["honest", "mixed"])
def test_a_block_that_reaches_another_slots_request_is_seen(mixed):
    """The cell's check (b) on requests served TOGETHER
    (``runners/serve_blockdiff.served_together``): on the engine as it
    is every unmasking is the reference's own; with the blocks of ONE
    dispatch handed to the wrong slots' requests it is not correct."""
    conf, cfg, params = model()
    runner = _bench("runners.serve_blockdiff")
    eng = engine(conf, cfg, params, slots=4)
    calls = []
    finish = eng._finish_blocks

    def swapped(out, counts, live, sown, passes):
        calls.append(len(live))
        if mixed and len(calls) == 4 and len(live) >= 2:
            tok, flags = out
            (i, _), (j, _) = live[:2]
            tok = tok.copy()
            tok[:, [i, j]] = tok[:, [j, i]]
            out = tok, flags
        return finish(out, counts, live, sown, passes)

    eng._finish_blocks = swapped
    try:
        got = runner.served_together(eng, arch, conf, params,
                                     arch.generation(conf), 11, 21)
    finally:
        eng.stop()
    assert got["whole"] and len(got["live"]) == len(got["token_shortfall"])
    assert np.median(got["live"]) >= got["together"] == 3
    ok = runner.within(got["token_shortfall"], runner.MARGIN_TOLERANCE_SIGMA,
                       runner.GROSS_SIGMA)
    assert ok is not mixed
    if not mixed:
        assert max(got["token_shortfall"]) == 0.0
        assert max(got["rank_shortfall"]) == 0.0
    else:
        assert max(got["token_shortfall"]) > runner.GROSS_SIGMA


def test_lane_steps_count_slot_passes(served):
    """Two whole blocks: ``4 n + 1`` = 9 live (slot, pass) pairs, the
    fifth of which commits the first block and opens the second; the
    budget ends at the second commit, which opens nothing and frees the
    slot."""
    conf, cfg, params, eng = served
    before, lane_steps = eng.stats(), eng._active_lane_steps
    # the idle engine's clock says how many passes the slot sits out so
    # that its commits fall where every slot's do: 0..3, in no pair
    wait = eng._start_wait(0, False)
    eng.pass_log = logs = {}
    try:
        fut = eng.submit(prompt_of(8), 8)
        fut.result(timeout=300)
    finally:
        eng.pass_log = None
    after = eng.stats()
    d = {k: after[k] - before[k] for k in after if k.startswith("blockdiff_")}
    assert d["blockdiff_passes"] == 5 * -(-(9 + wait) // 5)
    assert eng._lane_steps % (3 * 5) == 0
    assert eng._active_lane_steps - lane_steps == 9
    assert (d["blockdiff_slot_passes"], d["blockdiff_blocks_committed"],
            d["blockdiff_commits_fused"]) == (10, 2, 1)
    # one slot of three live in every pass
    assert after["slot_utilization"] == pytest.approx(
        eng._active_lane_steps / eng._lane_steps, abs=1e-3)
    assert eng._active_lane_steps == (after["blockdiff_slot_passes"]
                                      - after["blockdiff_commits_fused"])
    # rows the live pairs saw are what decode_kv_live_share reads: the
    # pass that commits the first block reads up to its end and, behind
    # it, up to the second's
    assert (after["decode_kv_tokens_live"] - before["decode_kv_tokens_live"]
            == 5 * 12 + 5 * 16)     # 8 rows prefilled, then two blocks
    # the log: a record a block through the model, the second block's
    # first as the fused forward found it; the last commit opened nothing
    log = logs[fut]
    assert [e["commit"] for e in log] == [False] * 4 + [True] + \
        [False] * 4 + [True]
    assert log[5] == {"tok": [MASK] * 4, "masked": [True] * 4,
                      "commit": False, "live": 1, "at": log[4]["at"]}
    assert [e["at"] - log[0]["at"] for e in log] == [0, 1, 2, 3, 4, 4, 5, 6,
                                                    7, 8]
    assert sum(log[6]["masked"]) == 3
    # and the slot is free, on the host and on the device
    assert all(s.request is None for s in eng._slots)
    assert (np.asarray(eng._toks["left"]) <= 0).all()


@pytest.mark.parametrize("given", [0, 1, 2, 3])
@pytest.mark.parametrize("name", ["static4", "static2", "static1",
                                  "dynamic"])
def test_a_slot_waits_so_that_its_commits_fall_where_every_slots_do(
        engines, name, given):
    """``_start_wait``: whatever the engine's clock reads and whatever
    the prompt gave of the first block, the slot's first commit falls in
    a pass that is a multiple of the passes a block takes; ``dynamic``
    remasking keeps no step."""
    conf, cfg, params = model(**GEN[name])
    if name not in engines:
        engines[name] = engine(conf, cfg, params)
    eng = engines[name]
    period = {"static4": 4, "static2": 2, "static1": 1}.get(name)
    first = -(-(4 - given) // eng._unmask)
    was = eng._passes_enqueued
    try:
        for clock in range(9):
            eng._passes_enqueued = clock
            for live in (False, True):
                wait = eng._start_wait(given, live)
                if period is None:
                    assert wait == 0
                    continue
                start = clock + (eng._T if live else 0)
                assert 0 <= wait < period
                assert (start + wait + first) % period == 0
    finally:
        eng._passes_enqueued = was


def test_a_fused_pass_leaves_the_cache_a_commit_and_an_opening_pass_would(
        served):
    """One forward that commits slot 0's block AND opens its next,
    beside a slot in mid-block and a dead one, against the same tokens
    through a commit pass and an opening pass run apart: the slabs row
    for row, the indices, and the open halves' logits."""
    conf, cfg, params, eng = served
    L, rng = 4, np.random.default_rng(11)
    index = np.asarray([8, 4, 12], np.int32)
    keys = iter(jax.random.split(jax.random.key(9), 64))

    def slabs():
        return jax.tree.map(
            lambda a: jnp.asarray(index) if a.ndim == 1 else
            jax.random.normal(next(keys), a.shape, a.dtype),
            eng._fresh_cache(3))

    def moved(cache, by):
        return jax.tree.map(
            lambda a: a + jnp.asarray(by, a.dtype) if a.ndim == 1 else a,
            cache)

    start = slabs()
    tok = jnp.asarray(rng.integers(1, MASK, (3, L)), jnp.int32)
    masked = jnp.asarray([[False] * 4, [False, True, False, True],
                          [True] * 4])
    forward = jax.jit(eng._pass_forward)
    # bit 1: slot 0's commit half is live beside its open half
    fused, mut = forward(eng._params, start, tok, masked,
                         jnp.asarray([3, 1, 0]))
    # apart: slot 0's commit in a pass of its own, the index moved by
    # hand, then the opening pass of its next block beside slot 1's
    _, first = forward(eng._params, start, tok, masked,
                       jnp.asarray([1, 0, 0]))
    assert [int(a) for a in eng._positions(first["cache"])] == [8, 4, 12]
    apart, second = forward(
        eng._params, moved(first["cache"], [L, 0, 0]),
        tok.at[0].set(MASK), masked.at[0].set(True), jnp.asarray([1, 1, 0]))
    assert [int(a) for a in eng._positions(mut["cache"])] == [12, 4, 12]
    for got, want, was in zip(jax.tree.leaves(mut["cache"]),
                              jax.tree.leaves(second["cache"]),
                              jax.tree.leaves(start)):
        got, want, was = (np.asarray(a) for a in (got, want, was))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        if got.ndim > 1:
            assert (got[2] == was[2]).all()         # the dead slot's slab
            assert (got[0] != was[0]).any()
    close(fused[:2], apart[:2])
    # the commit half never saw the open half: the finished block's rows
    # are what a commit alone writes (keys [slots, Hk, D, rows])
    for got, want in zip(jax.tree.leaves(mut["cache"]),
                         jax.tree.leaves(first["cache"])):
        if got.ndim == 4 and got.shape[-1] == 128:
            np.testing.assert_allclose(
                np.asarray(got)[0, ..., 8:12], np.asarray(want)[0, ..., 8:12],
                rtol=1e-5, atol=1e-6)


def test_a_chunked_prompt_and_a_pooled_prefix_serve_what_a_plain_prefill_does(
        served):
    """40 prompt tokens at ``prefill_chunk`` 16: two mid chunks and a
    last one; then the same prompt again, its first 32 rows re-attached
    from the pool (``kv_block`` 8, a multiple of L).  Tokens equal the
    reference's both times, and the re-attached slab gives the logits a
    plain prefill's does."""
    conf, cfg, params, eng = served
    prompt = prompt_of(42, seed=2)
    want = arch.block_diffusion_generate(conf, params, prompt.tolist(), 8,
                                         **arch.generation(conf))
    before = eng.stats()
    cold = eng.generate(prompt, 8, timeout=300).tolist()
    mid = eng.stats()
    pooled = eng.generate(prompt, 8, timeout=300).tolist()
    after = eng.stats()
    assert cold == want and pooled == want
    assert mid["chunked_admissions"] - before["chunked_admissions"] == 1
    assert after["kv_prefix_hits"] - mid["kv_prefix_hits"] == 1
    assert (after["kv_prefill_tokens_skipped"]
            - mid["kv_prefill_tokens_skipped"]) == 32
    # rows enter the pool at commit only: 40 prefilled + 2 given + 6
    # delivered + 2 past the cut = 48 rows = 6 blocks, no more
    assert mid["kv_blocks_used"] - before["kv_blocks_used"] == 6

    # the slabs: plain prefill against gather + suffix prefill
    key, P0 = jax.random.key(0), 40
    ids = np.zeros((1, 64), np.int32)
    ids[0, :P0] = prompt[:P0]
    eng._buckets = (*eng._buckets, 64)      # one program past the chunk
    plain, *_ = eng._prefill_fn(64, 1)(eng._params, jnp.asarray(ids),
                                       jnp.asarray([P0]), key, None)
    chain = eng._kv.match(prompt[:P0])
    assert len(chain) == 4
    block_ids = np.zeros((4,), np.int32)
    block_ids[:] = [nd.block_id for nd in chain]
    tail = np.zeros((1, 8), np.int32)
    tail[0, :] = prompt[32:40]
    reuse, *_ = eng._reuse_prefill_fn(8, 4)(
        eng._params, eng._kv.pool, jnp.asarray(block_ids),
        jnp.asarray(32, jnp.int32), jnp.asarray(tail), jnp.asarray([8]), key,
        eng._kv.snap_arg(0))

    def first_pass(slab):
        slab = jax.tree.map(lambda a: jnp.full_like(a, P0) if a.ndim == 1
                            else a, slab)
        tok = jnp.asarray([[int(prompt[40]), int(prompt[41]), 0, 0]])
        masked = jnp.asarray([[False, False, True, True]])
        return eng._pass_forward(eng._params, slab, tok, masked,
                                 jnp.ones((1,), bool))[0]

    close(first_pass(reuse), first_pass(plain))
    seq = jnp.asarray([prompt.tolist() + [MASK, MASK]])
    close(first_pass(plain)[0], arch.logits(conf, params, seq)[0, -4:])


def test_a_slot_freed_mid_block_serves_its_next_request_as_a_fresh_engine():
    """An EOS is read a tick late: by then the slot has run on into its
    next block and left uncommitted rows past its index, which the next
    owner's writes overwrite before any length reaches them."""
    conf, cfg, params = model()
    first, second = prompt_of(8), prompt_of(13, seed=4)
    plain = arch.block_diffusion_generate(conf, params, first.tolist(), 24,
                                          **arch.generation(conf))
    eos = plain[5]
    cut = plain[:plain.index(eos) + 1]
    eng = engine(conf, cfg, params, slots=1, eos_id=eos, steps_per_sync=3)
    try:
        assert eng.generate(first, 24, timeout=300).tolist() == cut
        stats = eng.stats()
        # passes ran for the slot after the block that held the EOS
        assert stats["blockdiff_blocks_committed"] * 4 > len(cut) + 3 or \
            stats["blockdiff_tokens_unmasked"] > (
                stats["blockdiff_blocks_committed"] * 4)
        got = eng.generate(second, 9, timeout=300).tolist()
    finally:
        eng.stop()
    fresh = engine(conf, cfg, params, slots=1, eos_id=eos, steps_per_sync=3)
    try:
        assert got == fresh.generate(second, 9, timeout=300).tolist()
    finally:
        fresh.stop()
    want = arch.block_diffusion_generate(conf, params, second.tolist(), 9,
                                         **arch.generation(conf))
    assert got == (want[:want.index(eos) + 1] if eos in want else want)


# -- the kernels -------------------------------------------------------------
def test_block_kernels_in_interpret_mode_equal_the_einsum_path(monkeypatch,
                                                              served):
    conf, cfg, params, plain = served
    prompt = prompt_of(11)
    want = plain.generate(prompt, 12, timeout=300).tolist()
    calls = []
    real = decode_attention.block_append
    monkeypatch.setattr(decode_attention, "block_applies",
                        lambda L, mesh, max_len, dtype: L == 4)
    monkeypatch.setattr(
        decode_attention, "block_append",
        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    eng = engine(conf, cfg, params)
    try:
        assert eng.generate(prompt, 12, timeout=300).tolist() == want
        runner = _bench("runners.serve_blockdiff")
        out = runner.logit_check(
            eng, arch, conf, params, 7,
            prompts=[np.random.default_rng(5).integers(1, MASK, 23).tolist()])
        assert out["logit_error_sigma"].max() < 1e-4
    finally:
        eng.stop()
    assert calls                        # the kernels were on the path


@pytest.mark.parametrize("L,dtype,tiles", [
    (4, jnp.bfloat16, True), (16, jnp.bfloat16, True),
    (16, jnp.float32, False), (3, jnp.bfloat16, False),
    (1, jnp.bfloat16, False)])
def test_block_kernels_take_a_block_that_lies_in_one_tile(monkeypatch, L,
                                                          dtype, tiles):
    assert not decode_attention.block_applies(4, None, 256, jnp.bfloat16)
    monkeypatch.setattr(decode_attention, "_on_tpu", lambda: True)
    assert decode_attention.block_applies(L, None, 256, dtype) == tiles
    assert not decode_attention.block_applies(4, object(), 256, dtype)
    assert not decode_attention.block_applies(4, None, 200, dtype)


def test_block_append_writes_a_block_and_spares_the_rest():
    B, Hk, D, T, L = 3, 2, 128, 256, 4
    ks = jax.random.split(jax.random.key(0), 4)
    k0 = jax.random.normal(ks[0], (B, Hk, D, T), jnp.float32)
    v0 = jax.random.normal(ks[1], (B, Hk, T, D), jnp.float32)
    kn = jax.random.normal(ks[2], (B, L, Hk, D))
    vn = jax.random.normal(ks[3], (B, L, Hk, D))
    idx = jnp.asarray([0, 124, 252], jnp.int32)
    live = jnp.asarray([True, True, False])
    k1, v1 = decode_attention.block_append(k0, v0, kn, vn, idx, live,
                                           interpret=True)
    ke, ve = np.array(k0), np.array(v0)
    for b in range(2):
        for p in range(L):
            ke[b, :, :, int(idx[b]) + p] = np.asarray(kn[b, p])
            ve[b, :, int(idx[b]) + p, :] = np.asarray(vn[b, p])
    assert (np.asarray(k1) == ke).all() and (np.asarray(v1) == ve).all()


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1))
    except Exception as e:  # noqa: BLE001 — any failure means: not here
        pytest.skip(f"no v5e:1x1 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_pass_program_for_v5e_runs_its_kernels_and_moves_no_slab(
        one_chip, monkeypatch):
    """``ContinuousBatcher._pass_impl`` at the served widths (one layer
    of the six, 16 slots x 4096 rows, two passes), compiled ahead of
    time for one v5e chip from abstract shapes: the Mosaic compiler
    takes ``block_append`` and ``block_attend`` (``L x G`` = 32 query
    rows a KV head), one of each a half, and the expert FFN of ``16 x
    8 x 8`` pairs (both halves' rows) goes through ``moe_decode_gmm``
    once a layer, not ``ragged_dot``; the head runs over the open half
    alone; no op but the aliased appends holds a whole slab."""
    import re
    import types

    from edl_tpu.models.generate import (sample_logits, sown_layout,
                                         sown_vector)
    from edl_tpu.ops import moe

    monkeypatch.setattr(decode_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    with open(os.path.join(BENCH, "configs",
                           "sdar-30b-a3b-chat-serve-d6.json")) as f:
        conf = dict(json.load(f), num_hidden_layers=1)
    B, T, L = 16, 2, 4
    dcfg = dataclasses.replace(
        arch.transformer_config(conf, max_len=4096, remat=False),
        decode=True, attention_impl="dense")
    pmodel = TransformerLM(dataclasses.replace(dcfg, decode_scatter=True))
    shapes = jax.eval_shape(lambda: TransformerLM(dcfg).init(
        jax.random.key(0), jnp.zeros((B, 1), jnp.int32),
        positions=jnp.zeros((B, 1), jnp.int32)))

    def on_chip(s, dtype=None):
        return jax.ShapeDtypeStruct(s.shape, dtype or s.dtype,
                                    sharding=one_chip)

    def sown_of(params):
        ids = jnp.zeros((B, 2 * L), jnp.int32)
        _, mut = pmodel.apply(
            {"params": params, "cache": jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes["cache"])},
            ids, positions=ids, token_mask=ids == 0,
            mutable=["cache", "intermediates"])
        return mut.get("intermediates", {})

    layout = sown_layout(jax.eval_shape(sown_of, shapes["params"]))
    assert dict(layout) == {"moe_fetched": 1, "moe_stats": 3}
    eng = types.SimpleNamespace(
        _pmodel=pmodel, _T=T, _block=L, _mask_id=151669,
        _remasking="static", _threshold=0.9, _unmask=1,
        _acc_shape=jax.ShapeDtypeStruct(
            (sum(w + 1 for _, w in layout),), jnp.float32),
        _sown=lambda mut: sown_vector(mut.get("intermediates"), layout),
        _positions=ContinuousBatcher._positions,
        _sample=lambda logits, key: sample_logits(logits, key,
                                                  temperature=0.0))
    eng._pass_forward = lambda *a: ContinuousBatcher._pass_forward(eng, *a)
    state = {"tok": on_chip(jax.ShapeDtypeStruct((B, L), jnp.int32)),
             "masked": on_chip(jax.ShapeDtypeStruct((B, L), jnp.bool_)),
             "left": on_chip(jax.ShapeDtypeStruct((B,), jnp.int32)),
             "wait": on_chip(jax.ShapeDtypeStruct((B,), jnp.int32))}
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(
            lambda *a: ContinuousBatcher._pass_impl(eng, *a),
            donate_argnums=(0,)).lower(
            jax.tree.map(on_chip, shapes["cache"]), state,
            on_chip(jax.eval_shape(lambda: jax.random.key(0))),
            jax.tree.map(lambda s: on_chip(s, jnp.bfloat16),
                         shapes["params"]),
            on_chip(jax.ShapeDtypeStruct((B,), jnp.bool_))).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    text = compiled.as_text()
    calls = re.findall(r"^\s*%?(block_append|block_attend|moe_decode_gmm)"
                       r"[.\d]* = .*? custom-call\(", text, re.M)
    # a pass in which some slot commits, and a pass in which none does
    assert sorted(calls) == ["block_append"] * 3 + ["block_attend"] * 3 + [
        "moe_decode_gmm"] * 2, calls
    assert "ragged" not in text
    # the logits are the open halves'
    assert "f32[16,4,151936]" in text and "[16,8,151936]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6
    slab = re.compile(r"\[16,4,(128,4096|4096,128)\]")
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) "
                     r"([\w\-]+)\(", line)
        if not m or not slab.search(m.group(2)):
            continue
        name, op = m.group(1), m.group(3)
        if op in ("parameter", "get-tuple-element", "tuple", "while",
                  "conditional", "bitcast") or (op == "custom-call"
                                 and name.startswith("block_append")):
            continue
        moved.append(f"{op} {name}")
    assert not moved, moved


# -- what a block engine refuses ---------------------------------------------
def test_spec_k_a_mesh_and_other_cache_classes_refuse_the_block_length():
    conf, cfg, params = model()
    with pytest.raises(ValueError, match=r"spec_k > 0.*block_length = 4"):
        engine(conf, cfg, params, spec_k=2, draft_cfg=cfg,
               draft_params=params)
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match=r"mesh engine.*block_length = 4"):
        engine(conf, cfg, params, mesh=mesh)
    for name, n in (("kv_block", {"kv_block": 6}),
                    ("prefill_chunk", {"prefill_chunk": 18}),
                    ("max_len", {"max_len": 126})):
        with pytest.raises(ValueError, match=f"block_length 4 must divide "
                           f"{name}"):
            engine(conf, cfg, params, **n)


@pytest.mark.parametrize("kind,extra", [
    ("ssm", {"ssm_heads": 2}), ("latent", {"mla_rank": 16}),
    ("window", {"attn_window": 8}), ("kda", {"kda_heads": 2})])
def test_a_recurrent_latent_or_window_stack_refuses_the_block_length(
        kind, extra):
    with pytest.raises(ValueError, match="block_length > 0"):
        TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                          num_heads=2, mlp_dim=64, max_len=32,
                          layer_attn=(kind, "global"), block_length=4,
                          **extra)


@pytest.mark.parametrize("bad", [
    {"block_steps": 5}, {"block_remasking": "random"},
    {"block_mask_id": 128}])
def test_denoising_settings_are_checked(bad):
    """The loop's settings ride on the configuration, beside the block
    length: a wrong one is refused where the configuration is made."""
    conf, cfg, params = model()
    with pytest.raises(ValueError, match=next(iter(bad))):
        dataclasses.replace(cfg, **bad)


def test_an_engine_over_a_block_configuration_is_a_block_engine():
    """No argument of the engine names the loop: the constructor call
    ``serving/replica.py`` and the benchmark's ``runners/serve.py`` make
    builds the block engine from the configuration alone."""
    conf, cfg, params = model(steps=2)
    assert (cfg.block_length, cfg.block_steps, cfg.block_remasking,
            cfg.block_mask_id) == (4, 2, "static", MASK)
    eng = ContinuousBatcher(cfg, params, slots=2, max_len=128, kv_block=8,
                            prefill_chunk=16, temperature=0.0)
    try:
        assert (eng._block, eng._unmask, eng._mask_id) == (4, 2, MASK)
        assert sorted(eng._block_state(2)) == ["left", "masked", "tok",
                                               "wait"]
    finally:
        eng.stop()


# -- the configuration file --------------------------------------------------
def test_the_cost_functions_are_the_configuration_files():
    with open(os.path.join(BENCH, "configs",
                           "sdar-30b-a3b-chat-serve-d6.json")) as f:
        conf = json.load(f)
    assert arch.param_count(conf) == 4_361_055_744
    assert arch.param_count(conf) == conf["memory"]["parameters"]
    cfg = arch.transformer_config(conf, max_len=4096)
    assert arch.param_count(conf) == param_count(cfg)
    assert (cfg.block_length, cfg.moe_experts, cfg.moe_top_k,
            cfg.expert_dim, cfg.head_dim, cfg.kv_heads) == (
        4, 128, 8, 768, 128, 4)
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["reduced_from"] == {"num_hidden_layers": 48}
    # a layer: 623,120,640 parameters; KV 12 KiB a token
    assert (arch.param_count(conf) - 2 * 151_936 * 2048 - 2048) // 6 \
        == 623_120_640
    assert arch.kv_bytes_per_token(conf) == 12 * 1024
    # a pass of 10 live slots at 300 rows: 6.5-7.5 GB
    touched = arch.expected_experts_touched(conf, 40)
    assert 115 < touched < 120
    assert 6.5e9 < arch.decode_step_min_bytes(conf, touched, 3000) < 7.6e9
    with pytest.raises(ValueError, match="shared_expert"):
        arch.transformer_config(dict(conf, shared_expert_size=1), max_len=64)
