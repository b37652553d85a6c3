"""Run by hand on the chip (PR 48's notes; not a test):

    chiprun --chips 1 -- python3 benchmarks/tests/chip_sdar_variants.py \\
        [--seeds 2147489001,...] [--only right,causal_prefill,...] [--passes]

Shows that what ``runners/serve_blockdiff.py`` rests ``correct`` on
separates the SDAR program from deliberately wrong ones, at the published
widths of ``configs/sdar-30b-a3b-chat-serve-d6.json``, THROUGH the
runner's own check (a) (``logit_check``: the engine's prefill, chunk and
pass programs, three prompts live together in a cache of four lanes,
against the reference's full forward under ``M``) and its limits:

    right           the configuration as it is
    causal_prefill  the prefill, chunk and reuse programs under a causal
                    mask in place of M (the pass program as it is)
    causal_chunks   the chunk lane's mid and last programs ALONE under a
                    causal mask (one prompt of the three goes through them)
    kept_denoise_kv a commit that moves the index without its pass: the
                    rows keep the K/V of the denoise pass before it
    bf16_scores     the multi-token programs' attention scores rounded to
                    bfloat16 before the softmax (the pass kernels hold
                    theirs in float32 and are left as they are)
    int8_experts    expert weights rounded to int8 per output channel (the
                    program's expert layers ALONE, a layer at a time)

and beside them two readings of the REFERENCE in the nearest precision
below the stated one, against itself: its scores and probabilities held
in bfloat16 (on ``logit_error_sigma``'s scale) and its expert layers
with the int8 weights (on ``expert_error``'s).
``--cell-with-kept-denoise-kv`` runs the CELL itself on an engine whose
commits do not write (the probe's and the burst's tokens and positions
through check (b), the window's answers); ``--cell-with-weights-key K``
runs it as it is on OTHER weights (``archs/sdar_moe.WEIGHTS_KEY`` = K:
the cell's own runs all serve one draw).  ``--passes`` times the
pass program alone at 4 / 8 / 16 live slots of 300 rows (host clock
around 20 dispatches of ``steps_per_sync`` passes, the device drained).
"""
import argparse
import dataclasses
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402

from archs import sdar_moe as arch              # noqa: E402
from runners import serve_blockdiff as runner   # noqa: E402

VARIANTS = ("right", "causal_prefill", "causal_chunks", "kept_denoise_kv",
            "bf16_scores", "int8_experts")


@jax.jit
def int8_experts(moe):
    """One layer's ``moe`` parameters with the expert matrices rounded
    to int8 per output channel (the router as it is)."""
    def q(a):
        if a.ndim != 3:
            return a
        x = a.astype(jnp.float32)
        scale = jnp.abs(x).max(axis=1, keepdims=True) / 127.0
        return (jnp.round(x / scale) * scale).astype(a.dtype)
    return jax.tree.map(q, moe)


def engine_of(conf, cfg, params, slots=4, max_len=1024):
    from edl_tpu.serving.engine import ContinuousBatcher
    return ContinuousBatcher(
        cfg, params, slots=slots, max_len=max_len, temperature=0.0, top_k=0,
        steps_per_sync=conf["run"]["steps_per_sync"], kv_block=0,
        prefill_chunk=conf["run"]["prefill_chunk"])


def verdict(name, seed, out):
    expert = float(np.median(out["expert_error"]))
    oks = {"cache_logits": runner.logits_within(
               out["logit_error_by_prompt"]),
           "expert_layers": expert <= runner.EXPERT_TOLERANCE}
    print(f"[variants] seed {seed} {name}: logits through the cache, three "
          f"lanes live together, a prompt's median row "
          f"{[round(v, 5) for v in out['logit_error_by_prompt']]} "
          f"(tolerances {list(runner.LOGIT_TOLERANCE_SIGMA)}), the largest "
          f"row {float(out['logit_error_sigma'].max()):.5f} sigma, rows "
          f"{[round(float(v), 4) for v in out['logit_error_sigma']]}; "
          f"expert layers alone, "
          f"median {expert:.5f} (tolerance {runner.EXPERT_TOLERANCE}): "
          f"{ {k: 'passes' if v else 'FAILS' for k, v in oks.items()} }; the "
          f"cell would say correct: {all(oks.values())}", flush=True)


def variants(conf, cfg, params, seed, only):
    from edl_tpu.models import transformer
    from edl_tpu.models.transformer import TransformerLM
    eng = engine_of(conf, cfg, params)
    try:
        for name in only:
            kw, undo = {}, None
            if name == "causal_prefill":
                keep = eng._model, dict(eng._prefill_cache)
                eng._model = TransformerLM(dataclasses.replace(
                    eng._dcfg, block_length=0))
                eng._prefill_cache.clear()

                def undo(keep=keep):
                    eng._model = keep[0]
                    eng._prefill_cache.clear()
                    eng._prefill_cache.update(keep[1])
            elif name == "causal_chunks":
                # a program family captures the model where it is built
                keep = eng._model, dict(eng._prefill_cache)
                eng._prefill_cache.clear()
                eng._model = TransformerLM(dataclasses.replace(
                    eng._dcfg, block_length=0))
                eng._chunk_mid_fn(eng._chunk_tokens)
                for b in eng._buckets:
                    if b <= eng._chunk_tokens:
                        eng._chunk_final_fn(b)
                eng._model = keep[0]

                def undo(keep=keep):
                    eng._prefill_cache.clear()
                    eng._prefill_cache.update(keep[1])
            elif name == "kept_denoise_kv":
                kw = {"commit_writes": False}
            elif name == "bf16_scores":
                real = transformer.Block._masked_attention

                def rounded(q, keys, values, mask, scale):
                    B, L, H, D = q.shape
                    Hk = keys.shape[1]
                    qg = q.reshape(B, L, Hk, H // Hk, D)
                    s = (jnp.einsum("blhgd,bhdk->bhglk", qg, keys)
                         * jnp.asarray(scale, q.dtype)).astype(jnp.bfloat16)
                    s = jnp.where(mask[:, None, None], s, -jnp.inf)
                    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
                    return jnp.einsum("bhglk,bhkd->blhgd", w, values
                                      ).reshape(B, L, H, D)

                transformer.Block._masked_attention = staticmethod(rounded)
                keep = dict(eng._prefill_cache)
                eng._prefill_cache.clear()

                def undo(real=real, keep=keep):
                    transformer.Block._masked_attention = staticmethod(
                        real)
                    eng._prefill_cache.clear()
                    eng._prefill_cache.update(keep)
            try:
                if name == "int8_experts":
                    # the PROGRAM's expert layers alone under the rounded
                    # weights, a layer at a time (a rounded copy of the
                    # stack does not fit beside it: no engine runs it)
                    rng = np.random.default_rng([seed, 9])
                    ids = jnp.asarray([rng.integers(
                        1, conf["vocab_size"], 204)], jnp.int32)
                    ref = arch.reference(conf, params, ids,
                                         rows=slice(-4, None))
                    err = arch.expert_error(eng._dcfg, params, ref,
                                            weights=int8_experts)
                    print(f"[variants] seed {seed} int8_experts: the "
                          f"program's expert layers alone, median "
                          f"{np.median(err):.5f} (tolerance "
                          f"{runner.EXPERT_TOLERANCE}): "
                          f"{'passes' if np.median(err) <= runner.EXPERT_TOLERANCE else 'FAILS'}",
                          flush=True)
                    continue
                out = runner.logit_check(eng, arch, conf, params, seed, **kw)
                verdict(name, seed, out)
            finally:
                if undo:
                    undo()
    finally:
        eng.stop()


def lower_precision_reference(conf, cfg, params, seed):
    """The REFERENCE against itself: scores and probabilities held in
    bfloat16, and the expert layers with int8 weights."""
    rng = np.random.default_rng([seed, 9])
    ids = jnp.asarray([rng.integers(1, conf["vocab_size"], 216)], jnp.int32)
    rows = slice(204, None)
    ref = arch.reference(conf, params, ids, rows=rows)
    low = arch.reference(conf, params, ids, rows=rows, scores="bfloat16")
    want, have = np.asarray(ref["logits"][0]), np.asarray(low["logits"][0])
    err = np.sqrt(np.mean(np.square(have - want), -1)) / want.std(-1)
    rounded = arch.expert_error(cfg, params, ref, weights=int8_experts,
                                by_reference=True)
    print(f"[variants] seed {seed} the reference with bfloat16 scores "
          f"against itself: logits, max {err.max():.5f} median "
          f"{np.median(err):.5f} sigma over {err.size} rows (the cell's "
          f"limit {runner.LOGIT_TOLERANCE_SIGMA}); with int8 experts "
          f"against itself: expert layers alone, median "
          f"{np.median(rounded):.5f} mean {rounded.mean():.5f} over "
          f"{rounded.size} (token, layer) pairs (the cell's limit "
          f"{runner.EXPERT_TOLERANCE})", flush=True)


def passes_alone(conf, cfg, params):
    """The pass program at the cell's shapes (16 slots x 4096 rows), k
    slots live at 300 rows each."""
    rc = conf["run"]
    eng = engine_of(conf, cfg, params, slots=rc["slots"],
                    max_len=rc["max_len"])
    try:
        L, T, S = 4, rc["steps_per_sync"], rc["slots"]
        key = jax.random.key(0)
        for k in (k for k in (4, 8, 16) if k <= S):
            state = eng._block_state(S)
            state["left"] = jnp.where(jnp.arange(S) < k, 1 << 20, 0
                                      ).astype(jnp.int32)
            cache = jax.tree.map(
                lambda a: jnp.full_like(a, 300) if a.ndim == 1 else a,
                eng._cache)
            live = eng._live_mask(list(range(k)))
            for timed in (False, True):
                t0 = time.perf_counter()
                for _ in range(20 if timed else 2):
                    cache, state, _, counts, acc, cnt = eng._pass_jit(
                        cache, state, key, eng._params, live)
                jax.block_until_ready(cnt)
                dt = time.perf_counter() - t0
            eng._cache = cache
            print(f"[variants] the pass program alone, {k} of {S} slots "
                  f"live at 300-800 rows: {1e3 * dt / (20 * T):.3f} ms a "
                  f"pass (host clock, 20 dispatches of {T} passes); the "
                  f"last dispatch counted {np.asarray(cnt).tolist()} "
                  f"[live pairs, commits, unmasked]", flush=True)
    finally:
        eng.stop()


def cell(seed: int, kept_denoise_kv: bool, weights_key: int | None) -> int:
    """The CELL (``run.py``, its probes, its burst and its window): on
    an engine whose commit pass leaves the cache as it found it but for
    the index, so that the rows keep the K/V of the last denoise pass
    (what check (b) and the served tokens read of a wrong engine), or as
    it is on the weights of another key."""
    import run as bench
    from edl_tpu.serving import engine as E
    fwd = E.ContinuousBatcher._pass_forward

    def kept(self, params, cache, tok, masked, on):
        logits, mut = fwd(self, params, cache, tok, masked, on)
        commit = ~masked.any(axis=1)

        def keep(new, old):
            return new if new.ndim == 1 else jnp.where(
                commit.reshape((-1,) + (1,) * (new.ndim - 1)), old, new)

        return logits, dict(mut, cache=jax.tree.map(keep, mut["cache"],
                                                    cache))

    if kept_denoise_kv:
        E.ContinuousBatcher._pass_forward = kept
    if weights_key is not None:
        arch.WEIGHTS_KEY = weights_key
    # traced on other weights: the line then holds what that draw's
    # passes touch (moe_experts_touched_mean)
    return bench.main(["--workload", "serve-blockdiff-chat-open", "--seed",
                       str(seed), "--seconds", "45", "--trace",
                       "0" if weights_key is None else "1"])


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", default="2147489001")
    p.add_argument("--only", default=",".join(VARIANTS))
    p.add_argument("--passes", action="store_true")
    p.add_argument("--cell-with-kept-denoise-kv", action="store_true")
    p.add_argument("--cell-with-weights-key", type=int, default=None)
    args = p.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("this check needs the chip")
    if args.cell_with_kept_denoise_kv or args.cell_with_weights_key:
        raise SystemExit(cell(int(args.seeds.split(",")[0]),
                              args.cell_with_kept_denoise_kv,
                              args.cell_with_weights_key))
    with open(os.path.join(BENCH, "configs",
                           "sdar-30b-a3b-chat-serve-d6.json")) as f:
        conf = json.load(f)
    cfg = arch.transformer_config(conf, max_len=1024, remat=False)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = arch.init_params(cfg, seed, conf["run"]["param_dtype"],
                                  split_layers=True)
        variants(conf, cfg, params, seed,
                 [v for v in args.only.split(",") if v])
        lower_precision_reference(conf, cfg, params, seed)
        if args.passes and i == 0:
            passes_alone(conf, cfg, params)
        del params
        gc.collect()            # an engine's closures hold the weights


if __name__ == "__main__":
    main()
