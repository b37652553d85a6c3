#!/usr/bin/env python3
"""The latent layers' expanded path alone, on the chip: the XLA loop
(``ops/latent_attention.expanded_attention``) against the kernel
(``latent_expand_tiled``) at the two served shapes, one lane of a chunk
against a 32768-row slab, the call ending at each of ``--ends`` rows.

Wall clock of ``--calls`` calls back to back (one ``block_until_ready``
at the end), the trip count traced as the engine traces it.  One JSON
line a (shape, end, path); ``--tiles 256x8,512x4`` also times the
kernel at those (rows a tile) x (heads a block) in place of the rule's
(``expand_block`` / ``expand_heads``).  Exits 2 without a TPU.

    python scripts/chip_latent_expand.py > chiprun_out/latent_expand.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from edl_tpu.ops import latent_attention as la

T, W, RANK, NOPE, ROPE, V = 32768, 640, 512, 128, 64, 128
SHAPES = {
    # name: (queries a call, heads, ends of the call in rows)
    "pangu": (512, 128, (2048, 8192, 16384)),
    "kimi": (256, 32, (256, 1024, 2048, 4096, 8192)),
}


def inputs(L: int, H: int, seed: int = 0):
    k = jax.random.split(jax.random.key(seed), 3)
    bf = jnp.bfloat16
    q = jax.random.normal(k[0], (1, L, H, NOPE + ROPE), bf)
    rows = la.cache_rows(jax.random.normal(k[1], (1, T, RANK + ROPE), bf),
                         W, bf)
    w = (jax.random.normal(k[2], (RANK, H, NOPE + V), jnp.float32)
         * RANK ** -0.5).astype(bf)
    return q, rows, w


def timed(fn, args, calls: int) -> float:
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="pangu,kimi")
    ap.add_argument("--tiles", default="")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--ends", default="")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("no TPU: this sweep measures the chip", file=sys.stderr)
        return 2
    tiles = [tuple(int(n) for n in t.split("x"))
             for t in args.tiles.split(",") if t]
    rule_block, rule_heads = la.expand_block, la.expand_heads
    for name in args.shapes.split(","):
        L, H, ends = SHAPES[name]
        if args.ends:
            ends = tuple(int(e) for e in args.ends.split(","))
        q, rows, w = inputs(L, H)
        scale = (NOPE + ROPE) ** -0.5

        def call(path):
            def chunk(q, rows, w, idx):
                return path(q, rows, w, idx[:, None] + jnp.arange(L),
                            idx.max() + L, rank=RANK, nope=NOPE, scale=scale)
            return jax.jit(chunk)

        loop = call(la.expanded_attention)
        for end in ends:
            idx = jnp.asarray([end - L], jnp.int32)
            want = loop(q, rows, w, idx)
            line = {"shape": name, "L": L, "H": H, "end": end,
                    "device": jax.devices()[0].device_kind}
            print(json.dumps({
                **line, "path": "loop",
                "rows_a_tile": rule_block(1, L, H, NOPE + V, T, rows.dtype),
                "ms": 1e3 * timed(loop, (q, rows, w, idx), args.calls)}),
                flush=True)
            for tk, hb in [(0, 0)] + tiles:
                la.expand_block = (lambda *a, tk=tk: tk) if tk else rule_block
                la.expand_heads = (lambda *a, hb=hb: hb) if tk else rule_heads
                kernel = call(la.latent_expand_tiled)
                got = kernel(q, rows, w, idx)
                err = float(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32)).max())
                tk_ = la.expand_block(1, L, H, NOPE + V, T, rows.dtype, True)
                print(json.dumps({
                    **line, "path": "kernel", "rows_a_tile": tk_,
                    "heads_a_block": la.expand_heads(H, NOPE + V, tk_, L),
                    "ms": 1e3 * timed(kernel, (q, rows, w, idx), args.calls),
                    "max_abs_diff_to_loop": err}), flush=True)
            la.expand_block, la.expand_heads = rule_block, rule_heads
    return 0


if __name__ == "__main__":
    sys.exit(main())
