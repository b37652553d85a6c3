"""Run by hand on the chip (PR 32's notes; not a test):
    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/tests/chip_granite_variants.py [seed | variant ...]
Shows that what ``runners/serve_ssm.py`` rests ``correct`` on separates
the granite-4.0-h-small program from thirteen deliberately wrong ones,
at the published widths of ``configs/granite-4.0-h-small-serve-ep2.json``.
For each seed one 800-token probe and the right program's greedy answer
to it (``models.generate``: prefill, then the cached recurrence), the
reference's full forward pass with the TRUE weights over prompt +
answer, and for every variant (or those named)
``archs/granite_moe_hybrid.block_agreement``: its Mamba-2 mixers alone,
its attention mixer alone, its expert layers alone and their held
experts alone, the state a mixer's cache carries, its whole block at the
level of logits and the block through its cache, medians, judged by the
cell's own ``serve_ssm.block_checks``.

    right            the configuration as it is
    rope             RoPE on the attention layer
    scale            attention scores / sqrt(128), not * 1/128
    no_residual      the residual multiplier 0.22 left out
    no_logits_div    logits not divided by 16
    no_d_skip        D * x left out of the mixer
    norm_first       the mixer's norm before its gate
    no_conv_bias     the convolution's bias left out
    no_dt_bias       dt_bias left out
    state_bf16       the recurrent state carried in bfloat16
    no_renorm        softmax over all 72, the top 10 not renormalised
    held_norm        gates normalised over the chosen experts HELD here
    no_shared        the shared MLP left out
    int8             expert weights rounded to int8 per output channel

``state_bf16`` and ``int8`` are the nearest precisions below the stated
ones (float32 state, bfloat16 weights); int8 runs last and rounds the
weights IN PLACE.
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
from archs import granite_moe_hybrid as arch   # noqa: E402
from runners import serve_ssm                  # noqa: E402

NEW, PROMPT = 17, 800
CONFIG = os.path.join(BENCH, "configs", "granite-4.0-h-small-serve-ep2.json")


def _held_only(held):
    def gates(probs, top_k, norm_topk):
        vals, idx = jax.lax.top_k(probs, top_k)
        vals = vals * (idx < held)
        return vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-20), idx
    return gates


def _zeroed(params, name):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a) if path[-1].key == name else a,
        params)


def round_experts_in_place(params):
    """Every routed expert matrix to int8 per output channel and back,
    one leaf at a time, the old leaf dropped before the next."""
    for name in [n for n in params if n.startswith("layer_")]:
        moe = params[name]["moe"]
        for key in ("w_gate", "w_in", "w_out"):
            x = moe[key].astype(jnp.float32)
            scale = jnp.abs(x).max(axis=1, keepdims=True) / 127.0
            moe[key] = (jnp.round(x / scale) * scale).astype(moe[key].dtype)
            del x


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("this check needs the chip")
    from edl_tpu.models import transformer
    from edl_tpu.models.generate import generate
    from edl_tpu.ops import moe as moe_ops
    with open(CONFIG) as f:
        conf = json.load(f)
    seeds = [int(a) for a in sys.argv[1:] if a.isdigit()] or [2147483659]
    only = [a for a in sys.argv[1:] if not a.isdigit()]
    cfg = arch.transformer_config(conf, max_len=1024, remat=False)
    block_cfg = arch.transformer_config(conf, max_len=PROMPT + NEW - 1,
                                        remat=False, attention_impl="dense")
    variants = {
        "right": {}, "rope": {"cfg": {"rope_global": True}},
        "scale": {"cfg": {"attn_scale": 0.0}},
        "no_residual": {"cfg": {"residual_multiplier": 1.0}},
        "no_logits_div": {"cfg": {"logits_scaling": 1.0}},
        "no_d_skip": {"zero": "D"},
        "norm_first": {"patch": (transformer, "_gate_norm", lambda o, z, norm:
                                 norm(o) * jax.nn.silu(z))},
        "no_conv_bias": {"zero": "conv_b"}, "no_dt_bias": {"zero": "dt_bias"},
        "state_bf16": {"cfg": {"ssm_state_dtype": jnp.bfloat16}},
        "no_renorm": {"cfg": {"moe_norm_topk": False}},
        "held_norm": {"patch": (moe_ops, "top_k_gates",
                                _held_only(conf["num_local_experts"]))},
        "no_shared": {"cfg": {"moe_shared_dim": 0}}, "int8": {},
    }
    unknown = sorted(set(only) - set(variants))
    if unknown:
        raise SystemExit(f"no variant {unknown}: {sorted(variants)}")
    # int8 rounds the weights in place, so it stays last
    variants = {k: v for k, v in variants.items() if not only or k in only}
    read_keys = ("mixer_error", "attention_error", "expert_error",
                 "routed_error", "state_error", "logit_error_sigma",
                 "cache_error_sigma")
    params = ref = block = None
    for seed in seeds:
        # the seed before's 9.5 GB of weights go before these arrive
        del params, ref, block
        params = arch.init_params(cfg, seed, conf["run"]["param_dtype"])
        probe = np.random.default_rng([seed, 5]).integers(
            1, conf["vocab_size"], PROMPT).tolist()
        out = np.asarray(jax.jit(
            lambda p, ids: generate(cfg, p, ids, NEW, temperature=0.0))(
                params, jnp.asarray([probe], jnp.int32)))[0].tolist()
        ids = jnp.asarray([probe + out], jnp.int32)[:, :-1]
        ref = arch.reference(conf, params, ids)
        at = np.asarray(ref["logits"])[0, len(probe) - 1:]
        short = (at.max(-1) - at[np.arange(NEW), out]) / at.std(-1)
        print(f"[variants] seed {seed} right serves {out}: margin "
              f"{short.max():.4f} sigma, argmax agrees on "
              f"{int((at.argmax(-1) == np.asarray(out)).sum())}/{NEW} "
              f"(tolerance {serve_ssm.MARGIN_TOLERANCE_SIGMA})", flush=True)
        for name, change in variants.items():
            undo = None
            if "patch" in change:
                mod, attr, fn = change["patch"]
                undo = (mod, attr, getattr(mod, attr))
                setattr(mod, attr, fn)
            if name == "int8":
                round_experts_in_place(params)
            block = arch.block_agreement(
                conf, params, ids, ref, tag=f" {name}",
                cfg=dataclasses.replace(block_cfg, **change.get("cfg", {})),
                program_params=(_zeroed(params, change["zero"])
                                if "zero" in change else params))
            if undo:
                setattr(*undo)
            checks = serve_ssm.block_checks(block)
            failed = [k for k, ok in checks.items() if not ok]
            print(f"[variants] seed {seed} {name}: " + ", ".join(
                f"{k} {float(np.median(block[k])):.5f}" for k in read_keys)
                + f" (state_error max {block['state_error'].max():.5f})"
                + f" -> {'CORRECT' if not failed else 'not correct by '}"
                + ", ".join(failed), flush=True)


if __name__ == "__main__":
    main()
