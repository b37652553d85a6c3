"""On the chip: the splash attention kernels alone (forward with
residuals, ``dq``, ``dkv``, and the fused backward) at the training
cells' shapes, one line a choice of tiles: the device microseconds a
call of each kernel takes (the profiler's ``XLA Ops`` line, not the wall
clock), what else the gradient runs beside them (the fused kernel's
reduction of its dQ partials), and the largest difference of each
gradient from the all-512 arrangement's.

    python scripts/chip_splash_sweep.py [--only NAME] [--iters N]
        [--quick | --chosen | --others | --accuracy]

``--accuracy`` times nothing: it holds the gradients of three
arrangements (all 512 and two kernels, the two kernels' best tiles, the
chosen one) against an f32 reference of the same bf16 inputs, three
seeds a shape, and prints each gradient's error relative to its size.

``edl_tpu/ops/attention.splash_block_sizes`` holds the winners; this is
how they were found, and how to find them again on another chip.  Exits
2 without a TPU: a CPU timing of a Pallas kernel says nothing.
"""

from __future__ import annotations

import argparse
import glob
import itertools
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

from edl_tpu.ops import attention
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as sk, splash_attention_mask as sm,
)

# name: sequences a chip, S, H, Dh
SHAPES = {
    "train-steady": (4, 4096, 32, 128),
    "train-mesh4": (1, 4096, 48, 128),
    "flagship": (8, 1024, 6, 128),
    "lm-d64": (8, 1024, 12, 64),
    "long-8k": (1, 8192, 32, 128),
}
BLOCKS = (512, 1024, 2048)
COMPUTE = (256, 512, 1024)


def kernel_for(L, H, sizes, **kw):
    mask = sm.MultiHeadMask(masks=[sm.CausalMask(shape=(L, L))] * H)
    return sk.make_splash_mha(mask=mask, head_shards=1, q_seq_shards=1,
                              block_sizes=sizes, **kw)


def grads_fn(kernel):
    def f(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: jax.vmap(kernel)(*a), q, k, v)
        return (out, *vjp(do))
    return jax.jit(f)


def forward_fn(kernel):
    """The kernel a gradient's forward runs (``save_residuals``: with the
    logsumexp), alone."""
    return jax.jit(lambda q, k, v, do: (jax.vmap(kernel)(q, k, v)[0],))


def others_fn(impl):
    """Forward + backward of ``[B, L, H, D]`` causal attention through
    the dispatcher (the splash row includes its transposes)."""
    def f(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: attention.dot_product_attention(
            *a, causal=True, impl=impl), q, k, v)
        return (out, *vjp(do))
    return jax.jit(f)


def traced(fn, args, iters):
    """``(outputs, {op: device us a call})`` of ``iters`` traced calls."""
    from jax.profiler import ProfileData
    out = jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(iters):
            o = fn(*args)
        jax.block_until_ready(o)
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(
            d, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        ops: dict[str, float] = {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    ops[ev.name] = ops.get(ev.name, 0.0) + ev.duration_ns
    return out, {k: v / iters / 1e3 for k, v in ops.items()}


def split(ops):
    """The three kernels by the names the trace gives them, and the rest."""
    out = {"fwd_us": 0.0, "dq_us": 0.0, "dkv_us": 0.0, "rest_us": 0.0}
    for name, us in ops.items():
        key = ("fwd_us" if "mha_fwd" in name else
               "dq_us" if "mha_dq" in name else
               "dkv_us" if "mha_dkv" in name else "rest_us")
        out[key] += us
    return {k: round(v, 1) for k, v in out.items()}


def inputs(seed, B, L, H, D):
    """q (scaled, as the model hands it over), k, v and the output's
    cotangent, ``[B, H, L, D]`` in bf16."""
    q, k, v, do = (jax.random.normal(kk, (B, H, L, D), jnp.bfloat16)
                   for kk in jax.random.split(jax.random.key(seed), 4))
    return (q * D ** -0.5).astype(jnp.bfloat16), k, v, do


@jax.jit
def reference(q, k, v, do):
    """Causal attention's output and gradients for ``[h, L, D]`` in f32,
    every matmul at the highest precision."""
    def attend(q, k, v):
        s = jnp.einsum("hqd,hkd->hqk", q, k, precision="highest")
        s = jnp.where(jnp.tril(jnp.ones(s.shape[1:], bool)), s, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v,
                          precision="highest")
    out, vjp = jax.vjp(attend, *(x.astype(jnp.float32) for x in (q, k, v)))
    return (out, *vjp(do.astype(jnp.float32)))


def accuracy(name, L, H, D, seeds=(0, 1, 2), heads_at_once=8):
    """One row an arrangement and a seed: for the output and each
    gradient of ONE sequence, the worst element's error as a share of
    the reference's largest element, the error's rms as a share of the
    reference's, and the median element's relative error."""
    tried = {
        "today": sk.BlockSizes(**TODAY, block_q_dq=512, block_kv_dq=512),
        "pair": sk.BlockSizes(
            block_q=1024, block_kv=1024, block_kv_compute=512,
            block_q_dkv=1024, block_kv_dkv=1024, block_kv_dkv_compute=1024,
            block_q_dq=1024, block_kv_dq=1024),
        "chosen": attention.splash_block_sizes(L)}
    fns = {tag: grads_fn(kernel_for(L, H, z)) for tag, z in tried.items()}
    for seed in seeds:
        args = inputs(seed, 1, L, H, D)
        ref = [np.concatenate(parts, axis=0) for parts in zip(*(
            [np.asarray(o) for o in reference(*(x[0, h:h + heads_at_once]
                                                for x in args))]
            for h in range(0, H, heads_at_once)))]
        for tag, fn in fns.items():
            row = {"shape": name, "S": L, "H": H, "Dh": D, "seed": seed,
                   "arrangement": tag}
            for what, got, want in zip(("out", "dq", "dk", "dv"),
                                       fn(*args), ref):
                err = np.abs(np.asarray(got[0], np.float32) - want)
                row[what] = {
                    "worst_of_max": float(err.max() / np.abs(want).max()),
                    "rms_of_rms": float(np.sqrt((err ** 2).mean())
                                        / np.sqrt((want ** 2).mean())),
                    "median_rel": float(np.median(
                        err / np.maximum(np.abs(want), 1e-30)))}
            yield row


TODAY = dict(block_q=512, block_kv=512, block_kv_compute=512,
             block_q_dkv=512, block_kv_dkv=512, block_kv_dkv_compute=512)


def choices(L, quick, only_chosen=False):
    """``(what is swept, BlockSizes)``: one kernel's tiles at a time
    beside today's for the others (a tile the compiler refuses for one
    kernel must not hide another's reading), then every fused backward."""
    tiles = [(bq, bkv, c) for bq, bkv, c in
             itertools.product(BLOCKS, BLOCKS, COMPUTE)
             if c <= bkv and bq <= L and bkv <= L]
    if quick:
        tiles = [t for t in tiles if t[2] == 512 or t == (512, 512, 256)]
    yield "today", sk.BlockSizes(**TODAY, block_q_dq=512, block_kv_dq=512)
    yield "chosen", attention.splash_block_sizes(L)
    if only_chosen:
        return
    for bq, bkv, c in tiles:
        # forward alone: no backward blocks, and main() runs no gradient
        yield "fwd", sk.BlockSizes(block_q=bq, block_kv=bkv,
                                   block_kv_compute=c)
    for bq, bkv, c in tiles:
        yield "dkv", sk.BlockSizes(**{
            **TODAY, "block_q_dkv": bq, "block_kv_dkv": bkv,
            "block_kv_dkv_compute": c}, block_q_dq=512, block_kv_dq=512)
    for bq, bkv in itertools.product(BLOCKS, BLOCKS):
        if bq <= L and bkv <= L:
            yield "dq", sk.BlockSizes(**TODAY, block_q_dq=bq, block_kv_dq=bkv)
    for bq, bkv, c in tiles:
        yield "fused", sk.BlockSizes(**{
            **TODAY, "block_q_dkv": bq, "block_kv_dkv": bkv,
            "block_kv_dkv_compute": c}, use_fused_bwd_kernel=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--chosen", action="store_true",
                    help="only today's tiles and splash_block_sizes' own")
    ap.add_argument("--accuracy", action="store_true",
                    help="the gradients' error against an f32 reference, "
                         "three arrangements x three seeds, and stop")
    ap.add_argument("--others", action="store_true",
                    help="time ONE sequence of each shape through "
                         "dot_product_attention's splash, flash and dense "
                         "(forward + backward, all device ops) and stop")
    ap.add_argument("--out", default="chiprun_out/splash_sweep.jsonl")
    a = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing to time", file=sys.stderr)
        return 2
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        peak = json.load(f)["by_device_kind"][
            jax.devices()[0].device_kind]["bf16_flops_per_s"]
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "a") as sink:
        for name, (B, L, H, D) in SHAPES.items():
            if a.only and a.only not in name:
                continue
            if a.accuracy:
                for row in accuracy(name, L, H, D):
                    print(json.dumps(row), flush=True)
                    sink.write(json.dumps(row) + "\n")
                continue
            q, k, v, do = inputs(0, B, L, H, D)
            flops = 2.0 * L * L * D * H * 0.5 * B      # one masked matmul
            if a.others:
                for impl in ("splash", "flash", "dense"):
                    row = {"shape": name, "B": 1, "S": L, "H": H, "Dh": D,
                           "impl": impl}
                    try:
                        row["fwd_bwd_us"] = round(sum(traced(
                            others_fn(impl), [x[:1].swapaxes(1, 2) for x in
                                              (q, k, v, do)], a.iters
                        )[1].values()), 1)
                    except Exception as e:
                        row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
                    print(json.dumps(row), flush=True)
                    sink.write(json.dumps(row) + "\n")
                continue
            base = None
            for tag, sizes in choices(L, a.quick, a.chosen):
                row = {"shape": name, "B": B, "S": L, "H": H, "Dh": D,
                       "swept": tag,
                       "fwd": [sizes.block_q, sizes.block_kv,
                               sizes.block_kv_compute],
                       "dkv": [sizes.block_q_dkv, sizes.block_kv_dkv,
                               sizes.block_kv_dkv_compute],
                       "dq": [sizes.block_q_dq, sizes.block_kv_dq]}
                fn = (forward_fn(kernel_for(L, H, sizes, save_residuals=True))
                      if tag == "fwd" else grads_fn(kernel_for(L, H, sizes)))
                try:
                    outs, ops = traced(fn, (q, k, v, do), a.iters)
                except Exception as e:   # the compiler's refusal is a result
                    row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
                else:
                    row.update(split(ops))
                    share = 100 * flops / peak * 1e6
                    row["fwd_peak_share"] = round(
                        2 * share / max(row["fwd_us"], 1e-3), 1)
                    if tag != "fwd":
                        bwd = row["dq_us"] + row["dkv_us"] + row["rest_us"]
                        row["bwd_us"] = round(bwd, 1)
                        row["bwd_peak_share"] = round(
                            4 * share / max(bwd, 1e-3), 1)
                        row["rest"] = {
                            n.split(" = ")[0]: round(us, 1)
                            for n, us in ops.items()
                            if "mha_" not in n and us >= 5.0}
                    outs = [np.asarray(o, np.float32) for o in outs]
                    if base is None:
                        base = outs
                    row["max_diff_vs_today"] = [
                        float(np.abs(o - b).max())
                        for o, b in zip(outs, base)]
                print(json.dumps(row), flush=True)
                sink.write(json.dumps(row) + "\n")
                sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
