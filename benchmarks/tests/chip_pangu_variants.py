"""Run by hand on the chip (PR 41's notes; not a test):
    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/tests/chip_pangu_variants.py [seed | variant ...]
Shows that what ``runners/serve_mla.py`` rests ``correct`` on separates
the openPangu-Ultra-MoE program from deliberately wrong ones, at the
published widths of ``configs/openpangu-ultra-moe-718b-serve-ep16.json``.
For each seed one 1,500-token probe and the right program's greedy answer
to it (``models.generate``), the reference's full forward pass with the
TRUE weights over prompt + answer, and for every variant (or those named)
``archs/pangu_ultra_moe.block_agreement``, medians, judged by the cell's
own ``serve_mla.block_checks``.

    right            the configuration as it is
    latent_8bit      the latent rows cached in 8 bits (a scale a row)
    no_q_norm        the low-rank query's RMSNorm left out
    no_post_norm     the attention branch's post-norm left out
    rope_off         q_pe and k_pe not rotated
    rope_pairs       half-split pairs rotated where the reference (and
                     the right program) rotate interleaved ones: the
                     wrong dims under the same weights
    no_scale         the gates' factor 2.5 left out
    no_renorm        the chosen scores not divided by their sum
    int8             expert weights rounded to int8 per output channel

``latent_8bit`` and ``int8`` are the nearest precisions below the stated
ones (bfloat16 cache and weights); int8 runs last and rounds the weights
IN PLACE.
"""
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import flax.linen as nn        # noqa: E402
import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402
from archs import pangu_ultra_moe as arch      # noqa: E402
from runners import serve_hybrid               # noqa: E402
from runners import serve_mla                  # noqa: E402

NEW, PROMPT = 17, 1500
CONFIG = os.path.join(BENCH, "configs",
                      "openpangu-ultra-moe-718b-serve-ep16.json")


def round_experts_in_place(params):
    """Every routed expert matrix to int8 per output channel and back,
    one leaf at a time, the old leaf dropped before the next."""
    for name in [n for n in params if n.startswith("layer_")]:
        moe = params[name].get("moe")
        if moe is None:
            continue
        for key in ("w_gate", "w_in", "w_out"):
            x = moe[key].astype(jnp.float32)
            scale = jnp.abs(x).max(axis=1, keepdims=True) / 127.0
            moe[key] = (jnp.round(x / scale) * scale).astype(moe[key].dtype)
            del x


def skip_norm(skipped: str):
    """``transformer.RMSNorm`` with the one named ``skipped`` the
    identity (its scale still declared)."""
    class _Norm(nn.Module):
        dtype: object = jnp.bfloat16
        eps: float = 1e-6

        @nn.compact
        def __call__(self, x):
            scale = self.param("scale", nn.initializers.ones,
                               (x.shape[-1],), jnp.float32)
            if self.name == skipped:
                return x.astype(self.dtype)
            var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1,
                           keepdims=True)
            return (x * jax.lax.rsqrt(var + self.eps)).astype(
                self.dtype) * scale
    return _Norm


def rope_half_split(x, positions, theta: float):
    """The rotary embedding over pairs ``(x[i], x[i + d / 2])``."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("this check needs the chip")
    from edl_tpu.models import transformer
    from edl_tpu.models.generate import generate
    from edl_tpu.ops import latent_attention as la
    with open(CONFIG) as f:
        conf = json.load(f)
    seeds = [int(a) for a in sys.argv[1:] if a.isdigit()] or [2147483659]
    only = [a for a in sys.argv[1:] if not a.isdigit()]
    cfg = arch.transformer_config(conf, max_len=1664, remat=False)
    block_cfg = arch.transformer_config(conf, max_len=PROMPT + NEW - 1,
                                        remat=False, attention_impl="dense")
    cache_rows = la.cache_rows

    def rows_8bit(latent, row, dtype):
        x = latent.astype(jnp.float32)
        scale = jnp.abs(x).max(-1, keepdims=True) / 127.0 + 1e-30
        return cache_rows(jnp.round(x / scale) * scale, row, dtype)

    variants = {
        "right": {},
        "latent_8bit": {"patch": [(la, "cache_rows", rows_8bit)]},
        "no_q_norm": {"patch": [(transformer, "RMSNorm",
                                 skip_norm("q_norm"))]},
        "no_post_norm": {"patch": [(transformer, "RMSNorm",
                                    skip_norm("attn_post_norm"))]},
        "rope_off": {"cfg": {"mla_rope": False}},
        "rope_pairs": {"patch": [(transformer, "rope", rope_half_split)]},
        "no_scale": {"cfg": {"moe_routed_scale": 1.0}},
        "no_renorm": {"cfg": {"moe_norm_topk": False}},
        "int8": {},
    }
    unknown = sorted(set(only) - set(variants))
    if unknown:
        raise SystemExit(f"no variant {unknown}: {sorted(variants)}")
    variants = {k: v for k, v in variants.items() if not only or k in only}
    read_keys = ("attention_error", "absorbed_error", "expert_error",
                 "routed_error", "logit_error_sigma", "cache_error_sigma")
    params = ref = block = None
    for seed in seeds:
        del params, ref, block
        params = arch.init_params(cfg, seed, conf["run"]["param_dtype"])
        probe = np.random.default_rng([seed, 5]).integers(
            1, conf["vocab_size"], PROMPT).tolist()
        out = np.asarray(jax.jit(
            lambda p, ids: generate(cfg, p, ids, NEW, temperature=0.0))(
                params, jnp.asarray([probe], jnp.int32)))[0].tolist()
        out = out[-NEW:]
        ids = jnp.asarray([probe + out], jnp.int32)[:, :-1]
        ref = arch.reference(conf, params, ids)
        at = np.asarray(ref["logits"])[0, len(probe) - 1:]
        short = (at.max(-1) - at[np.arange(NEW), out]) / at.std(-1)
        print(f"[variants] seed {seed} right serves {out}: margin "
              f"{short.max():.4f} sigma, argmax agrees on "
              f"{int((at.argmax(-1) == np.asarray(out)).sum())}/{NEW} "
              f"(tolerance {serve_hybrid.MARGIN_TOLERANCE_SIGMA} under the "
              f"nearest honest routing)", flush=True)
        memo = {}
        for name, change in variants.items():
            undo = [(mod, attr, getattr(mod, attr))
                    for mod, attr, _ in change.get("patch", [])]
            for mod, attr, fn in change.get("patch", []):
                setattr(mod, attr, fn)
            if name == "int8":
                round_experts_in_place(params)
            try:
                block = arch.block_agreement(
                    conf, params, ids, ref, tag=f" {name}", memo=memo,
                    cfg=dataclasses.replace(block_cfg,
                                            **change.get("cfg", {})))
            finally:
                for item in undo:
                    setattr(*item)
            checks = serve_mla.block_checks(block)
            failed = [k for k, ok in checks.items() if not ok]
            print(f"[variants] seed {seed} {name}: " + ", ".join(
                f"{k} {float(np.median(block[k])):.5f}" for k in read_keys)
                + f" (absorbed max {block['absorbed_error'].max():.5f})"
                + f" -> {'CORRECT' if not failed else 'not correct by '}"
                + ", ".join(failed), flush=True)


if __name__ == "__main__":
    main()
