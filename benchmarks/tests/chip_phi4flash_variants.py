"""Run by hand on the chip (PR 45's notes; not a test):
    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/tests/chip_phi4flash_variants.py [seed | variant ...]
Shows that what ``runners/serve_sambay.py`` rests ``correct`` on
separates the Phi-4-mini-flash-reasoning program from deliberately wrong
ones, at the published widths of
``configs/phi-4-mini-flash-reasoning-serve.json``.  For each seed one
1,100-token probe and the right program's greedy answer to it
(``models.generate``: prefill, then the cached recurrence), the
reference's full forward pass with the TRUE weights over prompt +
answer, and for every variant (or those named)
``archs/phi4flash.block_agreement``: its Mamba-1 mixers alone, its
differential attention layers alone, its gated memory units alone, the
state a mixer's cache carries, its whole stack at the level of logits
and the stack through its cache, medians, judged by the cell's own
``serve_sambay.block_checks``.

    right            the configuration as it is
    state_bf16       the recurrent state carried in bfloat16
    lam0             lambda = 0: plain attention in place of differential
    stale_memory     a GMU handed the memory of the PREVIOUS token
    window511        a window of 511 in place of 512
    rows_short       the cross layers read the full layer's rows one short
    no_d_skip        D * x left out of the mixer (and of the memory)
    no_attn_bias     the attention projections' biases left out
    rmsnorm          RMSNorm in place of LayerNorm

``state_bf16`` is the nearest precision below the stated one (float32
state).
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
from archs import phi4flash as arch     # noqa: E402
from runners import serve_sambay        # noqa: E402

NEW, PROMPT = 17, 1100
CONFIG = os.path.join(BENCH, "configs",
                      "phi-4-mini-flash-reasoning-serve.json")


def _zeroed(params, name, owners=None):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if path[-1].key == name and (owners is None
                                     or path[-2].key in owners) else a,
        params)


def patches(transformer):
    """``{variant: (object, attribute, replacement)}``: the wrong
    programs that are another PROGRAM, not another setting."""
    block, gmu = transformer.Block, transformer.GatedMemoryUnit
    lam, cross, unit = block._lambda, block._cross_attention, gmu.__call__

    def no_lambda(self, width):
        return 0.0, lam(self, width)[1]

    def one_short(self, q, positions, token_mask, lent):
        return cross(self, q, positions - 1, token_mask, lent)

    def stale(self, u, memory):
        return unit(self, u, jnp.roll(memory, 1, axis=1))

    return {"lam0": (block, "_lambda", no_lambda),
            "rows_short": (block, "_cross_attention", one_short),
            "stale_memory": (gmu, "__call__", stale)}


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("this check needs the chip")
    from edl_tpu.models import transformer
    from edl_tpu.models.generate import generate
    from edl_tpu.utils.compile_cache import enable_compile_cache
    # the comparison compiles one small program a layer a variant: layers
    # of one kind are one entry of the persistent cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with open(CONFIG) as f:
        conf = json.load(f)
    seeds = [int(a) for a in sys.argv[1:] if a.isdigit()] or [2147483659]
    only = [a for a in sys.argv[1:] if not a.isdigit()]
    cfg = arch.transformer_config(conf, max_len=1152, remat=False)
    block_cfg = arch.transformer_config(conf, max_len=PROMPT + NEW - 1,
                                        remat=False, attention_impl="dense")
    patch = patches(transformer)
    variants = {
        "right": {},
        "state_bf16": {"cfg": {"ssm_state_dtype": jnp.bfloat16}},
        "lam0": {"patch": patch["lam0"]},
        "stale_memory": {"patch": patch["stale_memory"]},
        "window511": {"cfg": {"attn_window": conf["sliding_window"] - 1}},
        "rows_short": {"patch": patch["rows_short"]},
        "no_d_skip": {"zero": ("D", None)},
        "no_attn_bias": {"zero": ("bias", ("attn_qkv", "attn_q",
                                           "attn_out"))},
        "rmsnorm": {"cfg": {"norm": "rms"}},
    }
    unknown = sorted(set(only) - set(variants))
    if unknown:
        raise SystemExit(f"no variant {unknown}: {sorted(variants)}")
    variants = {k: v for k, v in variants.items() if not only or k in only}
    read_keys = ("mixer_error", "window_error", "attention_error", "gmu_error",
                 "state_error", "logit_error_sigma", "cache_error_sigma",
                 "cross_step_error")
    params = ref = block = None
    for seed in seeds:
        del params, ref, block
        params = arch.init_params(cfg, seed, conf["run"]["param_dtype"])
        probe = np.random.default_rng([seed, 5]).integers(
            1, conf["vocab_size"], PROMPT).tolist()
        out = np.asarray(jax.jit(
            lambda p, ids: generate(cfg, p, ids, NEW, temperature=0.0))(
                params, jnp.asarray([probe], jnp.int32)))[0].tolist()
        ids = jnp.asarray([probe + out], jnp.int32)[:, :-1]
        ref = arch.reference(conf, params, ids)
        at = ref["logits"][0, len(probe) - 1:]
        short = (at.max(-1) - at[np.arange(NEW), out]) / at.std(-1)
        print(f"[variants] seed {seed} right serves {out}: margin "
              f"{short.max():.4f} sigma, argmax agrees on "
              f"{int((at.argmax(-1) == np.asarray(out)).sum())}/{NEW} "
              f"(tolerance {serve_sambay.MARGIN_TOLERANCE_SIGMA})",
              flush=True)
        for name, change in variants.items():
            undo = None
            if "patch" in change:
                obj, attr, fn = change["patch"]
                undo = (obj, attr, getattr(obj, attr))
                setattr(obj, attr, fn)
            wrong = params
            if "zero" in change:
                wrong = _zeroed(params, *change["zero"])
            block = arch.block_agreement(
                conf, params, ids, ref, tag=f" {name}",
                cfg=dataclasses.replace(block_cfg, **change.get("cfg", {})),
                program_params=wrong)
            if undo:
                setattr(*undo)
            checks = serve_sambay.block_checks(block)
            failed = [k for k, ok in checks.items() if not ok]
            print(f"[variants] seed {seed} {name}: " + ", ".join(
                f"{k} {float(np.median(block[k])):.5f}" for k in read_keys)
                + f" (state_error max {block['state_error'].max():.5f})"
                + f" -> {'CORRECT' if not failed else 'not correct by '}"
                + ", ".join(failed), flush=True)


if __name__ == "__main__":
    main()
