"""Run by hand on the chip (PR 26's notes; not a test):

    chiprun --chips 1 -- python3 benchmarks/tests/chip_olmoe_variants.py [seed ...]

Shows that what ``runners/serve_arch.py`` rests ``correct`` on separates
the OLMoE program from three deliberately wrong ones, at the published
widths of ``configs/olmoe-1b-7b-serve-d6.json``.  For each variant and
each seeded 200-token prompt: greedy tokens of the variant's prefill +
cached decode (``models.generate``), the reference's full forward pass
with the TRUE weights over prompt + answer, and the two measures of the
cell: ``runners/serve.py``'s margin (how far the reference's logit of
each served token lies under the reference's best, in standard
deviations of the reference's logits at that position), and
``archs/olmoe.block_agreement`` (the variant's expert layers alone
and its whole block at the level of logits against the reference's,
medians).  For int8 also the reference's OWN expert layers computed
with the rounded weights: the nearest precision below the stated one.
Then the dropping variant once through a small ``ContinuousBatcher``,
for the counters the cell holds to what the host routed.

    right        the configuration as it is
    renormalised moe_norm_topk = True (gates divided by their sum)
    dropped      moe_capacity = 1.0 (the capacity path: overflow dropped)
    int8         expert weights rounded to int8 per output channel
"""
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402

from archs import olmoe        # noqa: E402
from runners import serve_arch  # noqa: E402

NEW, PROMPT, PROBES = 16, 200, 4


def int8_experts(params):
    def q(path, a):
        if not any(getattr(k, "key", None) == "moe" for k in path) or \
                a.ndim != 3:
            return a
        x = a.astype(jnp.float32)
        scale = jnp.abs(x).max(axis=1, keepdims=True) / 127.0
        return (jnp.round(x / scale) * scale).astype(a.dtype)
    return jax.tree_util.tree_map_with_path(q, params)


def probes_of(conf, cfg, params, seed):
    from edl_tpu.models.generate import generate
    rng = np.random.default_rng([seed, 5])
    probes = [rng.integers(1, conf["vocab_size"], PROMPT).tolist()
              for _ in range(PROBES)]
    # the block as the cell compares it: full forward, dense attention
    block_cfg = olmoe.transformer_config(conf, max_len=PROMPT + NEW - 1,
                                         remat=False, attention_impl="dense")
    variants = {
        "right": ({}, None),
        "renormalised": ({"moe_norm_topk": True}, None),
        "dropped": ({"moe_capacity": 1.0}, None),
        "int8": ({}, int8_experts),
    }
    m_tol = serve_arch.MARGIN_TOLERANCE_SIGMA
    e_tol = serve_arch.EXPERT_TOLERANCE
    b_tol = serve_arch.BLOCK_TOLERANCE_SIGMA
    for name, (change, requantise) in variants.items():
        vparams = params if requantise is None else requantise(params)
        worst, alone, medians, agree = [], [], [], 0
        for probe in probes:
            out = np.asarray(generate(
                dataclasses.replace(cfg, **change), vparams,
                jnp.asarray([probe], jnp.int32), NEW,
                temperature=0.0))[0].tolist()
            ids = jnp.asarray([probe + out], jnp.int32)[:, :-1]
            ref = olmoe.reference(conf, params, ids)
            at = np.asarray(ref["logits"])[0, len(probe) - 1:]
            short = (at.max(-1) - at[np.arange(NEW), out]) / at.std(-1)
            worst.append(float(short.max()))
            agree += int((at.argmax(-1) == np.asarray(out)).sum())
            block = olmoe.block_agreement(
                conf, params, ids, ref, tag=f" {name}",
                cfg=dataclasses.replace(block_cfg, **change),
                program_params=vparams)
            alone.append(float(np.median(block["expert_error"])))
            medians.append(float(np.median(block["logit_error_sigma"])))
            if requantise is not None and probe is probes[0]:
                lower_precision_reference(conf, params, vparams, ref, seed)
        del vparams
        oks = {"margin": max(worst) <= m_tol,
               "expert layers": max(alone) <= e_tol,
               "block logits": max(medians) <= b_tol}
        print(f"[variants] seed {seed} {name}: margin per probe "
              f"{[round(w, 4) for w in worst]} sigma, argmax agrees on "
              f"{agree}/{PROBES * NEW} (tolerance {m_tol}); expert layers "
              f"alone, median per probe {[round(m, 5) for m in alone]} "
              f"(tolerance {e_tol}); block logits, median per probe "
              f"{[round(m, 5) for m in medians]} sigma (tolerance {b_tol}): "
              f"{ {k: 'passes' if v else 'FAILS' for k, v in oks.items()} }"
              f"; the cell would say correct: {all(oks.values())}",
              flush=True)


def lower_precision_reference(conf, params, rounded, ref, seed):
    """The REFERENCE's expert layers (float32 arithmetic, no program
    code) with the rounded expert weights against themselves with the
    true ones, on the same inputs: what the nearest precision below the
    stated one reads on ``expert_error``'s scale."""
    import functools
    moe = jax.jit(functools.partial(
        olmoe._moe, top_k=conf["num_experts_per_tok"],
        norm_topk=bool(conf["norm_topk_prob"])))
    errs = []
    with jax.default_matmul_precision("highest"):
        for p, (y, out) in zip(
                olmoe._layers(rounded, conf["num_hidden_layers"]),
                ref["experts"]):
            got, _ = moe(y.reshape(-1, y.shape[-1]), p["moe"])
            want = out.reshape(got.shape)
            errs.append(np.asarray(jnp.linalg.norm(got - want, axis=-1)
                                   / jnp.linalg.norm(want, axis=-1)))
    errs = np.concatenate(errs)
    print(f"[variants] seed {seed} the reference with int8 experts against "
          f"itself: expert layers alone, median {np.median(errs):.5f} mean "
          f"{errs.mean():.5f} over {errs.size} (token, layer) pairs",
          flush=True)


def counters_of(conf, cfg, params, seed):
    """The dropping variant through the engine: what the cell's exact
    checks read (``serve_arch.expert_checks``)."""
    from edl_tpu.serving.engine import ContinuousBatcher
    rng = np.random.default_rng([seed, 6])
    for name, change in (("right", {}), ("dropped", {"moe_capacity": 1.0})):
        eng = ContinuousBatcher(
            dataclasses.replace(cfg, **change), params, slots=4,
            temperature=0.0, top_k=0, steps_per_sync=4, kv_block=0,
            prefill_chunk=0)
        try:
            futs = [eng.submit(rng.integers(1, conf["vocab_size"], PROMPT),
                               NEW) for _ in range(3)]
            for f in futs:
                f.result(timeout=600)
            stats = eng.stats()
        finally:
            eng.stop()
        checks = serve_arch.expert_checks(conf, stats, None)
        print(f"[variants] seed {seed} {name} through the engine: "
              f"{stats['moe_assignments']} assignments for "
              f"{stats['moe_tokens']} tokens, {stats['moe_prefill_drops']} "
              f"drops: nothing_dropped {checks['nothing_dropped']}, "
              f"every_token_routed {checks['every_token_routed']}",
              flush=True)


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("this check needs the chip")
    with open(os.path.join(BENCH, "configs",
                           "olmoe-1b-7b-serve-d6.json")) as f:
        conf = json.load(f)
    seeds = [int(a) for a in sys.argv[1:]] or [2147483659]
    cfg = olmoe.transformer_config(conf, max_len=1024, remat=False)
    for i, seed in enumerate(seeds):
        params = olmoe.init_params(cfg, seed, conf["run"]["param_dtype"],
                                   split_layers=True)
        probes_of(conf, cfg, params, seed)
        if i == 0:
            counters_of(conf, cfg, params, seed)
        del params


if __name__ == "__main__":
    main()
