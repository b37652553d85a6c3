"""On the chip: ``ops/decode_attention.decode_attend`` alone at the served
shapes, this checkout's kernel against another checkout's (the parent
commit, unpacked with ``git archive``) from one call, on the same slabs.

    python scripts/chip_decode_attend.py [--parent .scratch/parent]
        [--iters N] [--only NAME,...] [--blocks 256,512] [--out FILE]

One JSON line a shape and set of live slots: the device microseconds a
call of each kernel takes from the profiler's op line (trust these, not
the wall clock) and of every op of the jitted call together (the plan
the kernel runs under is XLA ops beside it), the live rows' bytes over
the kernel's time as a share of the HBM peak (what
``benchmarks/layer_metrics/shared_kv_attend_roofline.py`` counts: K and
V of the live positions, read once), the positions each fetched
(``tokens_fetched``) over the live ones, the wall-clock microseconds of
calls dispatched back to back, and the largest difference between the
two outputs.  ``--blocks`` times this checkout's kernel at other attend
blocks beside its own.  Exits 2 without a TPU.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from edl_tpu.ops import decode_attention

HBM_BYTES_PER_S = 819e9      # one v5e chip (benchmarks/peaks.json)


def _lengths(B, live, lo, hi, seed):
    """``live`` slots of ``B``, scattered, each of ``lo .. hi`` rows."""
    rng = np.random.default_rng(seed)
    n = np.zeros(B, np.int32)
    n[rng.permutation(B)[:live]] = rng.integers(lo, hi + 1, live)
    return n


def cases():
    """``(name, B, Hk, G, D, T, window, [(label, lengths)])``."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mistral-7b-v0.3-serve-d6.json")) as f:
        mistral_len = json.load(f)["run"]["max_len"]
    sam = [(f"{n}x300-2300", _lengths(32, n, 300, 2300, n))
           for n in (8, 13, 16, 32)]
    sam.append(("2x16k", _lengths(32, 2, 16000, 16400, 2)))
    few = [(f"{n}x300", _lengths(12, n, 300, 300, n)) for n in (1, 2, 4)]
    return [
        ("sambay_slab", 32, 10, 4, 128, 20480, 0, sam),
        # ring positions written: most slots are past the ring's length
        ("sambay_ring", 32, 10, 4, 128, 640, 512,
         [(f"{n}x300-2300", _lengths(32, n, 300, 2300, n))
          for n in (8, 13, 16, 32)]),
        ("mistral", 12, 8, 4, 128, mistral_len, 0,
         few + [("4x6k", _lengths(12, 4, 5800, 6200, 6))]),
        ("olmoe", 12, 16, 1, 128, 4096, 0,
         few + [("12x300-2300", _lengths(12, 12, 300, 2300, 12))]),
    ]


def op_us(fn, args, iters, trace_dir):
    """``{op name: device microseconds a call}`` of ``fn``'s ops."""
    from jax.profiler import ProfileData
    jax.block_until_ready(fn(*args))
    jax.profiler.start_trace(trace_dir)
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    ops = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = ev.name.split(" = ")[0].lstrip("%")
                ops[name] = ops.get(name, 0.0) + ev.duration_ns * 1e-3 / iters
    return ops


def measure(fn, args, iters):
    with tempfile.TemporaryDirectory() as d:
        ops = op_us(fn, args, iters, d)
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    wall = 1e6 * (time.perf_counter() - t0) / iters
    kernel = sum(v for k, v in ops.items() if "attend" in k)
    return {"kernel_us": round(kernel, 2),
            "all_ops_us": round(sum(ops.values()), 2),
            "wall_us": round(wall, 2)}


def load_parent(path):
    spec = importlib.util.spec_from_file_location(
        "parent_decode_attention",
        os.path.join(path, "edl_tpu", "ops", "decode_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--parent", default=os.path.join(ROOT, ".scratch",
                                                    "parent"))
    p.add_argument("--iters", type=int, default=100)
    p.add_argument("--only", default="")
    p.add_argument("--blocks", default="")
    p.add_argument("--out", default="")
    a = p.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this measurement needs the chip", file=sys.stderr)
        return 2
    parent = load_parent(a.parent) if os.path.isdir(a.parent) else None
    only = [s for s in a.only.split(",") if s]
    blocks = [int(b) for b in a.blocks.split(",") if b]
    sink = None
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        sink = open(a.out, "a")
    bf = jnp.bfloat16
    for name, B, Hk, G, D, T, window, lives in cases():
        if only and name not in only:
            continue
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (B, Hk * G, D), bf)
        k = jax.random.normal(ks[1], (B, Hk, D, T), bf)
        v = jax.random.normal(ks[2], (B, Hk, T, D), bf)

        def call(mod, block=None):
            def fn(q, k, v, n):
                if not window:
                    return mod.decode_attend(q, k, v, n, block=block)
                # n positions written so far, the newest at n - 1
                return mod.decode_attend(
                    q, k, v, jnp.minimum(n, T), block=block,
                    newest=(n - 1) % T,
                    visible=jnp.minimum(n, window))
            return jax.jit(fn)

        for label, n in lives:
            lengths = jnp.asarray(n)
            held = np.minimum(n, T)
            live_rows = int((np.minimum(n, window) if window else held).sum())
            need = 2 * live_rows * Hk * D * 2       # K and V, bf16
            tk = decode_attention.attend_block(Hk, D, T, bf)
            line = {"case": name, "live": label, "live_rows": live_rows,
                    "block": tk}
            args = (q, k, v, lengths)
            fns = {"new": call(decode_attention)}
            if parent is not None:
                fns["parent"] = call(parent)
                line["parent_block"] = parent.attend_block(Hk, D, T, bf)
            for b in blocks:
                if T % b == 0 and b != tk:
                    fns[f"new@{b}"] = call(decode_attention, b)
            outs = {}
            for key, fn in fns.items():
                outs[key] = np.asarray(fn(*args).astype(jnp.float32))
                m = measure(fn, args, a.iters)
                m["roofline"] = (round(need / HBM_BYTES_PER_S / (
                    m["kernel_us"] * 1e-6), 4) if m["kernel_us"] else None)
                line[key] = m
            line["tokens_fetched_over_live"] = round(float(
                decode_attention.tokens_fetched(
                    jnp.asarray(held), Hk, D, T, bf, True)) / max(
                        int(held.sum()), 1), 4)
            for key in outs:
                if key != "new":
                    line[f"max_diff_{key}"] = float(
                        np.abs(outs[key] - outs["new"]).max())
            text = json.dumps(line)
            print(text, flush=True)
            if sink:
                sink.write(text + "\n")
                sink.flush()
        del q, k, v
    return 0


if __name__ == "__main__":
    sys.exit(main())
