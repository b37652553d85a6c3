"""Pipeline-schedule measurement: step time + compiled activation
memory vs microbatch count — why GPipe-via-AD is the right stop
(``edl_tpu/ops/pipeline.py``).

Runs the pipelined TransformerLM (`train_lm._PipelinedLM`, GPipe over
ppermute with the backward from jax.grad) at each requested pp and
microbatch count, reporting wall step time and XLA's compiled temp
(live activation) size.  On a dev box use the virtual mesh::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm/pp_bench.py --pp 2 4 --microbatches 2 4 8 16

The headline result (fixed GLOBAL batch): temp memory is
flat-to-DECREASING in M, because the per-tick stash shrinks as 1/M
while ticks grow as M+S-1 — so 1F1B's in-flight cap would buy little
while sharing GPipe's bubble, and raising M amortises the bubble for
free.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from edl_tpu.models import TransformerConfig  # noqa: E402
from edl_tpu.models.transformer import lm_loss  # noqa: E402
from edl_tpu.parallel import MeshSpec  # noqa: E402
from edl_tpu.parallel.sharding import shard_host_batch  # noqa: E402
from edl_tpu.train import ElasticTrainer, TrainConfig  # noqa: E402


def measure(args, pp: int, M: int) -> dict:
    from train_lm import _PipelinedLM

    cfg = TransformerConfig(
        vocab_size=args.vocab, num_layers=args.layers,
        embed_dim=args.embed, num_heads=args.heads, mlp_dim=args.mlp,
        max_len=args.seq_len, dtype=jnp.float32,
        attention_impl="dense", remat=False)
    model = _PipelinedLM(cfg, n_microbatches=M)

    def loss_fn(params, extra, batch, rng):
        logits = model.apply({"params": params}, batch["ids"][:, :-1])
        return lm_loss(logits, batch["ids"][:, 1:]), (extra, {})

    tr = ElasticTrainer(loss_fn, TrainConfig(mesh_spec=MeshSpec(dp=-1, pp=pp),
                                             log_every=0))
    model.mesh = tr.mesh
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (args.batch_size, args.seq_len + 1)).astype(np.int32)

    def init():
        return model.init(jax.random.key(0),
                          jnp.asarray(ids[:1]))["params"], None

    shape = jax.eval_shape(lambda: init()[0])
    state = tr.create_state(init, optax.adam(1e-3),
                            param_logical=model.logical_axes(shape))
    gb = shard_host_batch({"ids": ids}, tr.mesh)
    rng = jax.random.key(1)
    mem = tr.step_fn.lower(state, gb, rng).compile().memory_analysis()
    state, metrics = tr.step_fn(state, gb, rng)
    float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, metrics = tr.step_fn(state, gb, rng)
    float(metrics["loss"])
    dt = (time.perf_counter() - t0) / args.steps
    return {
        "pp": pp, "microbatches": M,
        "step_ms": round(dt * 1e3, 1),
        "temp_mb": round(getattr(mem, "temp_size_in_bytes", 0) / 1e6, 1),
        "bubble_pct": round(100 * (pp - 1) / (M + pp - 1), 1),
        "loss": round(float(metrics["loss"]), 4),
    }


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--pp", type=int, nargs="+", default=[2, 4])
    p.add_argument("--microbatches", type=int, nargs="+",
                   default=[2, 4, 8, 16])
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--embed", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--mlp", type=int, default=256)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--seq_len", type=int, default=64)
    p.add_argument("--batch_size", type=int, default=32)
    p.add_argument("--steps", type=int, default=10)
    args = p.parse_args()

    n_dev = len(jax.devices())
    for pp in args.pp:
        if n_dev % pp:
            print(f"[pp_bench] skip pp={pp}: {n_dev} devices", flush=True)
            continue
        for M in args.microbatches:
            if args.batch_size % M:
                continue
            print(json.dumps(measure(args, pp, M)), flush=True)


if __name__ == "__main__":
    main()
