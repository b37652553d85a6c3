"""On the chip: the loss head alone at the Mistral train widths (4 x 4096
tokens a chip, embed 4096, vocabulary 32768), ``jax.value_and_grad`` in
the hidden states (bf16) and the head's float32 weight (cast to bf16
inside, as ``transformer.lm_loss_fused`` casts it), two ways:

- ``loop``: ``ops/ce.blockwise_cross_entropy`` in blocks of 4096 columns
  of the vocabulary and the mean: four head-sized matmuls;
- ``sweep``: ``ops/ce.sweep_cross_entropy`` over blocks of tokens, at
  each ``--budgets`` MiB for one block's float32 logits (the op's own
  ``SWEEP_LOGITS_BYTES`` is 256): three.

    python scripts/chip_ce_head.py [--iters N] [--budgets 64,128,256,512]

With more than one chip the rows are split over all of them (``dp``):
the loop under GSPMD, the sweep under ``shard_map``.  One JSON line a
variant: milliseconds a call on the host's clock around
``block_until_ready`` (the chip runs nothing else), the loss and the
gradients' norms (which must agree between the variants), and the
compiled temporaries a chip.  PERF.md section 6, PR 51 has the readings
(one chip: loop 112.8 ms, sweep 85.9; four: 122.5 and 95.0).  Exits 2
without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from edl_tpu.ops import ce
from edl_tpu.parallel import MeshSpec, build_mesh

ROWS, L, D, V, BLOCK = 4, 4096, 4096, 32768, 4096


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--budgets", default="64,128,256,512")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"needs a TPU, found {devices[0].platform}", file=sys.stderr)
        return 2
    mesh = build_mesh(MeshSpec(dp=len(devices)), devices)
    rows, whole = NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())
    B = ROWS * len(devices)
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    h = jax.jit(lambda k: jax.random.normal(k, (B, L, D)).astype(jnp.bfloat16),
                out_shardings=rows)(k1)
    w = jax.jit(lambda k: jax.random.normal(k, (D, V)) * 0.02,
                out_shardings=whole)(k2)
    t = jax.jit(lambda k: jax.random.randint(k, (B, L), 0, V),
                out_shardings=rows)(k3)
    tw = jax.device_put(jnp.full((B, L), 1.0 / (B * L), jnp.float32), rows)

    def loop(h, w, t, tw):
        return ce.blockwise_cross_entropy(h, w.astype(h.dtype), t,
                                          block_size=BLOCK).mean()

    def sweep(h, w, t, tw):
        return ce.sweep_cross_entropy(h, w.astype(h.dtype), t, tw, mesh=mesh)

    def timed(name, f):
        g = jax.jit(jax.value_and_grad(f, argnums=(0, 1)),
                    out_shardings=(whole, (rows, whole)))
        for _ in range(2):
            out = jax.block_until_ready(g(h, w, t, tw))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = g(h, w, t, tw)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / args.iters * 1e3
        loss, (dh, dw) = out
        temp = g.lower(h, w, t, tw).compile().memory_analysis()
        print(json.dumps({
            "variant": name, "chips": len(devices), "ms": round(ms, 3),
            "loss": float(loss),
            "dh_norm": float(jnp.linalg.norm(dh.astype(jnp.float32))),
            "dw_norm": float(jnp.linalg.norm(dw)),
            "temp_gb_a_chip": round(temp.temp_size_in_bytes / 1e9, 3)}),
            flush=True)

    timed("loop", loop)
    for mib in (int(b) for b in args.budgets.split(",")):
        # the op's one knob, turned here as the tests turn it
        ce.SWEEP_LOGITS_BYTES = mib << 20
        tokens = ce._token_blocks(ROWS, L, V)[1] * ROWS
        timed(f"sweep, {mib} MiB = {tokens} tokens a block", sweep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
