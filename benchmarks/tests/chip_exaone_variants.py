"""Run by hand on the chip (PR 30's notes; not a test):

    chiprun --chips 1 -- python3 benchmarks/tests/chip_exaone_variants.py [--margin] [seed ...]

Shows that what ``runners/serve_hybrid.py`` rests ``correct`` on
separates the K-EXAONE program from seven deliberately wrong ones, at
the published widths of ``configs/k-exaone-236b-a23b-serve-ep8.json``.
For each seed one 656-token probe and the right program's greedy answer
to it (``models.generate``: prefill + cached decode through the window
layers' rings), the reference's full forward pass with the TRUE weights
over prompt + answer, and for every variant
``archs/exaone_moe.block_agreement``: the variant's sparse layers alone,
its held experts' partial sum alone and its whole block at the level of
logits against the reference's, medians, each beside the cell's limit.  With ``--margin`` the four
variants of ``SERVED`` also serve the probe themselves and
the cell's margin is read, each token under the honest routing nearest
to it as ``serve_hybrid.served_margin`` judges it (a compile of the
whole model and one more reference pass a variant, and up to 24 passes
for a token over the limit: 3-5 minutes of chip time each).

    right          the configuration as it is
    full_window    full attention on the window layers (window 16384)
    rope_global    RoPE on the global layers too
    softmax        a softmax router (no selection bias)
    no_scale       the gates' factor 2.5 left out
    no_shared      the shared expert left out
    held_norm      gates normalised over the chosen experts HELD here
    int8           expert weights rounded to int8 per output channel

int8 runs last and rounds the weights IN PLACE (12 GB of weights leave
no room for a second copy of the experts); beside it the reference's
OWN expert layers computed with the rounded weights, the nearest
precision below the stated one.
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

from archs import exaone_moe as arch   # noqa: E402
from runners import serve_hybrid       # noqa: E402

NEW, PROMPT = 17, 656
SERVED = ("full_window", "rope_global", "no_scale", "no_shared")


def _held_only(held):
    def gates(scores, bias, top_k, norm_topk, scale=1.0):
        _, idx = jax.lax.top_k(scores + bias, top_k)
        g = jnp.take_along_axis(scores, idx, axis=-1) * (idx < held)
        return g / jnp.maximum(g.sum(-1, keepdims=True), 1e-20) * scale, idx
    return gates


def round_experts_in_place(params):
    """Every routed expert matrix to int8 per output channel and back,
    one leaf at a time, the old leaf dropped before the next."""
    for name in [n for n in params if n.startswith("layer_")]:
        moe = params[name].get("moe")
        for key in ("w_gate", "w_in", "w_out") if moe else ():
            x = moe[key].astype(jnp.float32)
            scale = jnp.abs(x).max(axis=1, keepdims=True) / 127.0
            moe[key] = (jnp.round(x / scale) * scale).astype(moe[key].dtype)
            del x


def lower_precision_reference(conf, rounded, ref, seed):
    """The REFERENCE's held experts (float32 arithmetic, no program
    code) with the rounded expert weights against themselves with the
    true ones, on the same inputs, on ``held_expert_error``'s scale:
    the shared expert (not rounded) out of both sides, over the tokens
    that chose a held expert; and on ``expert_error``'s, the whole
    sparse layer over every token."""
    whole, held = [], []
    with jax.default_matmul_precision("highest"):
        for i, (y, out) in ref["experts"].items():
            moe = rounded[f"layer_{i}"]["moe"]
            got, _ = jax.jit(lambda p, y: arch.moe_mlp(conf, p, y))(
                moe, y.reshape(-1, y.shape[-1]))
            diff = jnp.linalg.norm(got.reshape(out.shape) - out, axis=-1)
            routed = out - arch.shared_reference(moe, y)
            on = np.asarray((ref["chosen"][i] < conf["num_experts"]).any(-1))
            whole.append(np.asarray(
                diff / jnp.linalg.norm(out, axis=-1)).reshape(-1))
            held.append(np.asarray(
                diff / jnp.linalg.norm(routed, axis=-1))[on])
    whole, held = np.concatenate(whole), np.concatenate(held)
    print(f"[variants] seed {seed} the reference with int8 experts against "
          f"itself: sparse layers alone, median {np.median(whole):.5f} over "
          f"{whole.size} (token, layer) pairs; the held experts' partial sum "
          f"alone, median {np.median(held):.5f} mean {held.mean():.5f} over "
          f"{held.size}", flush=True)


def margin_of(conf, cfg, params, probe, true_params=None):
    """The variant serves the probe; the shortfall of each served token
    under the true reference's best logit, in sigma, as
    ``serve_hybrid.served_margin`` judges the cold probe."""
    from edl_tpu.models.generate import generate
    out = np.asarray(jax.jit(
        lambda p, ids: generate(cfg, p, ids, NEW, temperature=0.0))(
            params, jnp.asarray([probe], jnp.int32)))[0].tolist()
    ids = jnp.asarray([probe + out], jnp.int32)[:, :-1]
    ref = arch.reference(conf, true_params or params, ids)
    at = np.asarray(ref["logits"])[0, len(probe) - 1:]
    # as the cell judges it: under the honest routing nearest to the token
    short = [arch.tie_aware_shortfall(
        conf, true_params or params, ids, ref, len(probe) - 1 + j, t,
        limit=serve_hybrid.MARGIN_TOLERANCE_SIGMA,
        delta=serve_hybrid.TIE_DELTA)["shortfall"]
        for j, t in enumerate(out)]
    return out, ids, ref, float(max(short)), int(
        (at.argmax(-1) == np.asarray(out)).sum())


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("this check needs the chip")
    from edl_tpu.ops import moe as moe_ops
    with open(os.path.join(BENCH, "configs",
                           "k-exaone-236b-a23b-serve-ep8.json")) as f:
        conf = json.load(f)
    args = sys.argv[1:]
    margins = "--margin" in args
    seeds = [int(a) for a in args if a != "--margin"] or [2147483659]
    cfg = arch.transformer_config(conf, max_len=1024, remat=False)
    block_cfg = arch.transformer_config(conf, max_len=PROMPT + NEW - 1,
                                        remat=False, attention_impl="dense")
    variants = {
        "right": {}, "full_window": {"attn_window": 16384},
        "rope_global": {"rope_global": True},
        "softmax": {"moe_router": "softmax", "moe_select_bias": False},
        "no_scale": {"moe_routed_scale": 1.0},
        "no_shared": {"moe_shared_dim": 0}, "held_norm": {}, "int8": {},
    }
    m_tol = serve_hybrid.MARGIN_TOLERANCE_SIGMA
    e_tol = serve_hybrid.EXPERT_TOLERANCE
    h_tol = serve_hybrid.HELD_EXPERT_TOLERANCE
    b_tol = serve_hybrid.BLOCK_TOLERANCE_SIGMA
    honest = moe_ops.sigmoid_gates
    for seed in seeds:
        params = arch.init_params(cfg, seed, conf["run"]["param_dtype"])
        probe = np.random.default_rng([seed, 5]).integers(
            1, conf["vocab_size"], PROMPT).tolist()
        out, ids, ref, worst, agree = margin_of(conf, cfg, params, probe)
        print(f"[variants] seed {seed} right serves {out}: margin "
              f"{worst:.4f} sigma, argmax agrees on {agree}/{NEW} "
              f"(tolerance {m_tol})", flush=True)
        for name, change in variants.items():
            if name == "held_norm":
                moe_ops.sigmoid_gates = _held_only(conf["num_experts"])
            if name == "int8":
                round_experts_in_place(params)
                lower_precision_reference(conf, params, ref, seed)
            block = arch.block_agreement(
                conf, params, ids, ref, tag=f" {name}",
                cfg=dataclasses.replace(block_cfg, **change),
                program_params=params)
            oks = {"expert layers": float(np.median(
                       block["expert_error"])) <= e_tol,
                   "held experts": float(np.median(
                       block["held_expert_error"])) <= h_tol,
                   "block logits": float(np.median(
                       block["logit_error_sigma"])) <= b_tol}
            line = ""
            if margins and name in SERVED:
                v_out, _, _, v_worst, v_agree = margin_of(
                    conf, dataclasses.replace(cfg, **change), params, probe)
                oks["margin"] = v_worst <= m_tol
                line = (f"margin {v_worst:.4f} sigma, argmax agrees on "
                        f"{v_agree}/{NEW} (tolerance {m_tol}); ")
            moe_ops.sigmoid_gates = honest
            print(f"[variants] seed {seed} {name}: {line}expert layers "
                  f"alone, median {np.median(block['expert_error']):.5f} "
                  f"(tolerance {e_tol}); held experts alone, median "
                  f"{np.median(block['held_expert_error']):.5f} (tolerance "
                  f"{h_tol}); block logits, median "
                  f"{np.median(block['logit_error_sigma']):.5f} sigma "
                  f"(tolerance {b_tol}): "
                  f"{ {k: 'passes' if v else 'FAILS' for k, v in oks.items()} }"
                  f"; the cell would say correct: {all(oks.values())}",
                  flush=True)
        del params, ref


if __name__ == "__main__":
    main()
