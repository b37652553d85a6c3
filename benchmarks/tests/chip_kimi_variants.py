"""Run by hand on the chip (PR 37's notes; not a test):
    chiprun --chips 1 --timeout 3000 -- python3 benchmarks/tests/chip_kimi_variants.py [seed | variant ...]
Shows that what ``runners/serve_latent.py`` rests ``correct`` on
separates the Kimi-Linear program from deliberately wrong ones, at the
published widths of ``configs/kimi-linear-48b-a3b-serve-ep4.json``.  For
each seed one 1,100-token probe and the right program's greedy answer to
it (``models.generate``), the reference's full forward pass with the
TRUE weights over prompt + answer, and for every variant (or those
named) ``archs/kimi_linear.block_agreement``, medians, judged by the
cell's own ``serve_latent.block_checks``.

    right            the configuration as it is
    state_bf16       the KDA state carried in bfloat16
    latent_8bit      the latent rows cached in 8 bits (a scale a row)
    no_delta         the delta correction left out (u = v)
    head_decay       the decay taken a head and not a channel
    no_k_pe          k_pe dropped from the scores
    rope             rotation applied to q_pe and k_pe
    no_bias          the selection bias left out of the router
    no_shared        the shared expert left out
    int8             expert weights rounded to int8 per output channel

``state_bf16``, ``latent_8bit`` and ``int8`` are the nearest precisions
below the stated ones (float32 state, bfloat16 cache and weights); int8
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
from archs import kimi_linear as arch          # noqa: E402
from runners import serve_latent               # noqa: E402
from runners import serve_hybrid               # noqa: E402

NEW, PROMPT = 17, 1100
CONFIG = os.path.join(BENCH, "configs", "kimi-linear-48b-a3b-serve-ep4.json")


def _zeroed(params, name):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a) if path[-1].key == name else a,
        params)


def _no_k_pe(params, rank):
    """``kv_a``'s columns past the latent zeroed: k_pe is 0 everywhere,
    so the scores lose ``q_pe . k_pe``."""
    def fix(path, a):
        keys = [k.key for k in path if hasattr(k, "key")]
        if keys[-2:] == ["kv_a", "kernel"]:
            return a.at[:, rank:].set(0)
        return a
    return jax.tree_util.tree_map_with_path(fix, params)


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


def _patches(kda, la):
    """The wrong recurrences and the 8-bit cache, as replacements of the
    program's own functions."""
    def no_delta_update(S, k, v, g, beta):
        S = S * jnp.exp(g)[..., None]
        return S + (beta[..., None] * k)[..., None] * v[:, :, None, :]

    def slow(update):
        """The chunked form and the step as the token-by-token scan of a
        wrong update (no lengths: the comparison pads nothing)."""
        def chunked(q, k, v, g, beta, state, *, chunk=64, lengths=None,
                    snap_at=None):
            def one(S, t):
                qt, kt, vt, gt, bt = t
                S = update(S, kt, vt, gt, bt)
                return S, jnp.einsum("bhkv,bhk->bhv", S, qt)
            f32 = jnp.float32
            final, o = jax.lax.scan(one, state.astype(f32), tuple(
                jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta)))
            return jnp.moveaxis(o, 0, 1), final, None

        def step(state, q, k, v, g, beta, live=None):
            new = update(state, k, v, g, beta)
            return jnp.einsum("bhkv,bhk->bhv", new, q), new
        return chunked, step

    def head_decay(fn):
        def wrapped(*a, **kw):
            a = list(a)
            i = 4 if fn.__name__.startswith("kda_step") else 3
            a[i] = jnp.broadcast_to(a[i].mean(-1, keepdims=True), a[i].shape)
            return fn(*a, **kw)
        return wrapped

    def rows_8bit(latent, row, dtype):
        x = latent.astype(jnp.float32)
        scale = jnp.abs(x).max(-1, keepdims=True) / 127.0 + 1e-30
        return cache_rows(jnp.round(x / scale) * scale, row, dtype)

    cache_rows = la.cache_rows
    chunked, step = slow(no_delta_update)
    return {
        "no_delta": [(kda, "kda_chunked", chunked), (kda, "kda_step", step),
                     (kda, "kda_step_reference", step)],
        "head_decay": [(kda, "kda_chunked", head_decay(kda.kda_chunked)),
                       (kda, "kda_step", head_decay(kda.kda_step)),
                       (kda, "kda_step_reference",
                        head_decay(kda.kda_step_reference))],
        "latent_8bit": [(la, "cache_rows", rows_8bit)],
    }


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("this check needs the chip")
    from edl_tpu.models.generate import generate
    from edl_tpu.ops import kda
    from edl_tpu.ops import latent_attention as la
    with open(CONFIG) as f:
        conf = json.load(f)
    seeds = [int(a) for a in sys.argv[1:] if a.isdigit()] or [2147483659]
    only = [a for a in sys.argv[1:] if not a.isdigit()]
    cfg = arch.transformer_config(conf, max_len=1280, remat=False)
    block_cfg = arch.transformer_config(conf, max_len=PROMPT + NEW - 1,
                                        remat=False, attention_impl="dense")
    patches = _patches(kda, la)
    variants = {
        "right": {},
        "state_bf16": {"cfg": {"kda_state_dtype": jnp.bfloat16}},
        "latent_8bit": {"patch": patches["latent_8bit"]},
        "no_delta": {"patch": patches["no_delta"]},
        "head_decay": {"patch": patches["head_decay"]},
        "no_k_pe": {"params": lambda p: _no_k_pe(p, conf["kv_lora_rank"])},
        "rope": {"cfg": {"mla_rope": True}},
        "no_bias": {"params": lambda p: _zeroed(p, "gate_bias")},
        "no_shared": {"cfg": {"moe_shared_dim": 0}}, "int8": {},
    }
    unknown = sorted(set(only) - set(variants))
    if unknown:
        raise SystemExit(f"no variant {unknown}: {sorted(variants)}")
    variants = {k: v for k, v in variants.items() if not only or k in only}
    read_keys = ("mixer_error", "attention_error", "absorbed_error",
                 "expert_error", "routed_error", "state_error",
                 "logit_error_sigma", "cache_error_sigma")
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
        # the cell's judge (serve_hybrid.served_margin) on the tokens
        # over the limit: the nearest honest routing
        limit = serve_hybrid.MARGIN_TOLERANCE_SIGMA
        for j in np.flatnonzero(short > limit):
            found = arch.tie_aware_shortfall(
                conf, params, ids, ref, len(probe) - 1 + int(j), out[j],
                limit=limit, delta=serve_hybrid.TIE_DELTA)
            print(f"[variants] seed {seed} token {j}: {found['plain']:.4f} "
                  f"sigma under the plain reference, {found['shortfall']:.4f}"
                  f" under the nearest honest routing {found['swaps']} after "
                  f"{found['passes']} passes -> "
                  f"{'within' if found['shortfall'] <= limit else 'OVER'} "
                  f"{limit}", flush=True)
        for name, change in variants.items():
            undo = [(mod, attr, getattr(mod, attr))
                    for mod, attr, _ in change.get("patch", [])]
            for mod, attr, fn in change.get("patch", []):
                setattr(mod, attr, fn)
            if name == "int8":
                round_experts_in_place(params)
            try:
                block = arch.block_agreement(
                    conf, params, ids, ref, tag=f" {name}",
                    cfg=dataclasses.replace(block_cfg,
                                            **change.get("cfg", {})),
                    program_params=(change["params"](params)
                                    if "params" in change else params))
            finally:
                for item in undo:
                    setattr(*item)
            checks = serve_latent.block_checks(block)
            failed = [k for k, ok in checks.items() if not ok]
            print(f"[variants] seed {seed} {name}: " + ", ".join(
                f"{k} {float(np.median(block[k])):.5f}" for k in read_keys)
                + f" (state_error max {block['state_error'].max():.5f})"
                + f" -> {'CORRECT' if not failed else 'not correct by '}"
                + ", ".join(failed), flush=True)


if __name__ == "__main__":
    main()
