"""Run by hand on the chip (PR 52's notes; not a test):
    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/tests/chip_solar_variants.py [seed | variant ...]
Shows that what ``runners/serve_deltagqa.py`` rests ``correct`` on
separates the Solar-Open2 program from deliberately wrong ones, at the
published widths of ``configs/solar-open2-250b-serve-ep8.json``.  For
each seed one 1,500-token probe and the right program's greedy answer to
it (``models.generate``), the reference's full forward pass with the
TRUE weights over prompt + answer, and for every variant (or those
named) ``archs/solar_open2.block_agreement`` and
``long_prefix_agreement``, medians, judged by the cell's own
``serve_deltagqa.block_checks``.

    right            the configuration as it is
    beta_undoubled   beta = sigmoid(b): kda_allow_neg_eigval ignored
    no_gate          the output gate left out of the GQA layer
    rope             q and k of the GQA layer rotated (use_rope true)
    state_bf16       the KDA state carried in bfloat16
    rows_8bit        K and V rows cached in 8 bits (a scale a row)
    no_bias          the selection bias left out of the router
    short_tile       the tiled chunk path stopping one tile short
    int8             expert weights rounded to int8 per output channel

``state_bf16``, ``rows_8bit`` and ``int8`` are the nearest precisions
below the stated ones (float32 state, bfloat16 rows and weights); int8
runs last and rounds the weights IN PLACE.
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
from archs import solar_open2 as arch          # noqa: E402
from runners import serve_deltagqa             # noqa: E402
from runners import serve_hybrid               # noqa: E402

NEW, PROMPT = 17, 1500
CONFIG = os.path.join(BENCH, "configs", "solar-open2-250b-serve-ep8.json")


def _zeroed(params, name):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a) if path[-1].key == name else a,
        params)


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


def _patches(transformer, da):
    """The 8-bit rows and the short loop, as replacements of the
    program's own functions."""
    attend = transformer.Block._decode_attention
    tiled = da.prefix_chunk_attention

    def eight_bit(x):
        x32 = x.astype(jnp.float32)
        scale = jnp.abs(x32).max(-1, keepdims=True) / 127.0 + 1e-30
        return (jnp.round(x32 / scale) * scale).astype(x.dtype)

    def rows_8bit(self, q, k, v, token_mask=None):
        return attend(self, q, eight_bit(k), eight_bit(v), token_mask)

    def short(q, keys, values, q_pos, limit, *, scale):
        tk = da.prefix_block(q.shape[0], q.shape[1], q.shape[2],
                             keys.shape[3], jnp.dtype(values.dtype).itemsize)
        return tiled(q, keys, values, q_pos, jnp.maximum(limit - tk, 1),
                     scale=scale)

    return {"rows_8bit": [(transformer.Block, "_decode_attention",
                           rows_8bit)],
            "short_tile": [(da, "prefix_chunk_attention", short)]}


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("this check needs the chip")
    from edl_tpu.models import transformer
    from edl_tpu.models.generate import generate
    from edl_tpu.ops import decode_attention as da
    with open(CONFIG) as f:
        conf = json.load(f)
    seeds = [int(a) for a in sys.argv[1:] if a.isdigit()] or [2147483659]
    only = [a for a in sys.argv[1:] if not a.isdigit()]
    cfg = arch.transformer_config(conf, max_len=1664, remat=False)
    block_cfg = arch.transformer_config(conf, max_len=PROMPT + NEW - 1,
                                        remat=False, attention_impl="dense")
    long_cfg = arch.transformer_config(
        conf, max_len=conf["run"]["long_prefix"] + 128, remat=False,
        attention_impl="dense")
    patches = _patches(transformer, da)
    variants = {
        "right": {},
        "beta_undoubled": {"cfg": {"kda_neg_eigval": False}},
        "no_gate": {"cfg": {"attn_gate": False}},
        "rope": {"cfg": {"rope_global": True}},
        "state_bf16": {"cfg": {"kda_state_dtype": jnp.bfloat16}},
        "rows_8bit": {"patch": patches["rows_8bit"]},
        "no_bias": {"params": lambda p: _zeroed(p, "gate_bias")},
        "short_tile": {"patch": patches["short_tile"]},
        "int8": {},
    }
    unknown = sorted(set(only) - set(variants))
    if unknown:
        raise SystemExit(f"no variant {unknown}: {sorted(variants)}")
    variants = {k: v for k, v in variants.items() if not only or k in only}
    read_keys = ("mixer_error", "attention_error", "expert_error",
                 "routed_error", "state_error", "logit_error_sigma",
                 "cache_error_sigma")
    long_keys = ("long_chunk_error", "long_step_error")
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
        for name, change in variants.items():
            undo = [(mod, attr, getattr(mod, attr))
                    for mod, attr, _ in change.get("patch", [])]
            for mod, attr, fn in change.get("patch", []):
                setattr(mod, attr, fn)
            if name == "int8":
                round_experts_in_place(params)
            pp = (change["params"](params) if "params" in change
                  else params)
            try:
                block = arch.block_agreement(
                    conf, params, ids, ref, tag=f" {name}",
                    cfg=dataclasses.replace(block_cfg,
                                            **change.get("cfg", {})),
                    program_params=pp)
                far = arch.long_prefix_agreement(
                    conf, params, seed, tag=f" {name}",
                    cfg=dataclasses.replace(long_cfg,
                                            **change.get("cfg", {})),
                    program_params=pp)
            finally:
                for item in undo:
                    setattr(*item)
            checks = serve_deltagqa.block_checks(block, far)
            failed = [k for k, ok in checks.items() if not ok]
            print(f"[variants] seed {seed} {name}: " + ", ".join(
                [f"{k} {float(np.median(block[k])):.5f}" for k in read_keys]
                + [f"{k} {float(np.median(far[k])):.5f}" for k in long_keys])
                + f" (state_error max {block['state_error'].max():.5f}, "
                f"long_step max {far['long_step_error'].max():.5f}, "
                f"long_chunk max {far['long_chunk_error'].max():.5f})"
                + f" -> {'CORRECT' if not failed else 'not correct by '}"
                + ", ".join(failed), flush=True)


if __name__ == "__main__":
    main()
