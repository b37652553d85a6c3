"""On the chip: the four layer matmuls of the one-chip train step that sit
beside an ``RMSNorm``, each alone at the Mistral train widths (4 x 4096
rows, embed 4096, MLP 14336, qkv 6144), three ways:

- ``fused``: the norm ``transformer._norm`` hands back where
  ``transformer._fences_norms`` does not hold (a decode model's: the
  bare module, as every model had it until PR 47): XLA is free to put
  the norm's reductions into the matmul's fusion;
- ``fenced``: the norm ``transformer._norm`` hands back where the rule
  holds, its input and output each through
  ``jax.lax.optimization_barrier``;
- ``bare``: the same matmuls (and adds) with no norm at all.

Both norms are the model's own, chosen by the model's own rule: the
script measures what the step runs and cannot drift from it.

    python scripts/chip_norm_matmul.py [--iters N] [--only NAME,...]
        [--out FILE]

The four shapes: ``mlp_out_fwd`` (``[4,4096,14336] x [14336,4096]`` +
the residual, then the next norm), ``mlp_gate_bwd`` (the backward of
``mlp_gate`` and ``mlp_in`` into the norm's output, their add, the
norm's backward, the residual's cotangent), ``attn_out_fwd`` and
``attn_qkv_bwd`` (the same two at 4096 x 4096 and 4096 x 6144).  One
JSON line a shape and variant: the device microseconds a call of every
op takes from the profiler's op line (trust these, not the wall
clock), the ops that hold a matmul (read from the compiled HLO: a
fusion whose computation has a ``convolution``) and which of them also
hold a ``reduce``, the matmuls' FLOPs over the matmul ops' time as a
share of 197 TFLOP/s, and the other ops' time (the norm's own passes).
Exits 2 without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from chip_decode_attend import op_us
from edl_tpu.models import transformer
from tests.helpers.hlo import fusion_bodies

PEAK_FLOPS = 197e12          # one v5e chip, bf16 (benchmarks/peaks.json)
B, L, D, MLP, QKV = 4, 4096, 4096, 14336, 6144
VARIANTS = ("fused", "fenced", "bare")
# the one-chip training cell's model as far as a norm reads it: no mesh,
# not a decode model, bf16, the default eps
CFG = transformer.TransformerConfig(
    vocab_size=32768, num_layers=2, embed_dim=D, num_heads=32,
    num_kv_heads=8, mlp_dim=MLP, max_len=L)


class _Norm(nn.Module):
    """``transformer._norm`` where a ``Block`` calls it: in a module."""
    cfg: transformer.TransformerConfig

    @nn.compact
    def __call__(self, x):
        return transformer._norm(self.cfg, "norm")(x)


def norms(variant):
    """``norm(x, scale)`` of a variant, in the type its consumer (an
    ``nn.Dense`` of the compute type) casts it to, as the model does
    AFTER the fence; ``bare`` has none."""
    if variant == "bare":
        return lambda x, scale: x
    cfg = dataclasses.replace(CFG, decode=variant == "fused")
    assert transformer._fences_norms(cfg) == (variant == "fenced")
    return lambda x, scale: _Norm(cfg).apply(
        {"params": {"norm": {"scale": scale}}}, x).astype(jnp.bfloat16)


def forward(variant):
    """A branch's last matmul, the residual add and the NEXT norm."""
    norm = norms(variant)

    def fn(y, w, x, scale):
        x = x + y @ w.astype(y.dtype)
        return (x,) if variant == "bare" else (x, norm(x, scale))
    return fn


def backward(variant):
    """The cotangents of a norm's consumers back through the norm: the
    matmuls against the transposed weights, their add, the norm's
    backward (its forward recomputed from ``x``, as remat does), the
    residual's cotangent on top."""
    norm = norms(variant)

    def fn(x, scale, ws, dres, douts):
        def branch(x, scale):
            n = norm(x, scale)
            return (x, *(n @ w.astype(n.dtype) for w in ws))
        _, vjp = jax.vjp(branch, x, scale)
        return vjp((dres, *douts))
    return fn


def cases():
    """``(name, fn of a variant, argument shapes, matmul FLOPs)``."""
    bf, f32 = jnp.bfloat16, jnp.float32
    rows, scale = (B, L, D), ((D,), f32)

    def fwd(k):
        return (forward, [((B, L, k), bf), ((k, D), f32), (rows, bf), scale],
                2 * B * L * k * D)

    def bwd(*ks):
        return (backward,
                [(rows, bf), scale, [((D, k), f32) for k in ks], (rows, bf),
                 [((B, L, k), bf) for k in ks]], 2 * B * L * D * sum(ks))

    return [("mlp_out_fwd", *fwd(MLP)), ("mlp_gate_bwd", *bwd(MLP, MLP)),
            ("attn_out_fwd", *fwd(D)), ("attn_qkv_bwd", *bwd(QKV))]


def _is_shape(s):
    return (isinstance(s, tuple) and len(s) == 2
            and isinstance(s[0], tuple))


def arrays(shapes, seed=0):
    leaves, tree = jax.tree.flatten(shapes, is_leaf=_is_shape)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        (jax.random.normal(k, s, jnp.float32) * 0.05).astype(dt)
        for k, (s, dt) in zip(keys, leaves)])


def matmul_ops(hlo):
    """``{op name: it also holds a reduce}`` of the ops of a compiled
    program that hold a matmul."""
    held = {}
    for name, body in fusion_bodies(hlo).items():
        if any(" convolution(" in b for b in body):
            held[name] = any(" reduce(" in b for b in body)
    return held


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--iters", type=int, default=100)
    p.add_argument("--only", default="")
    p.add_argument("--out", default="")
    a = p.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this measurement needs the chip", file=sys.stderr)
        return 2
    only = [s for s in a.only.split(",") if s]
    sink = None
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        sink = open(a.out, "a")
    for name, make, shapes, flops in cases():
        if only and name not in only:
            continue
        args = arrays(shapes)
        for variant in VARIANTS:
            fn = jax.jit(make(variant))
            held = matmul_ops(fn.lower(*args).compile().as_text())
            with tempfile.TemporaryDirectory() as d:
                ops = op_us(fn, args, a.iters, d)
            matmul = sum(us for op, us in ops.items() if op in held)
            line = {
                "case": name, "variant": variant,
                "matmul_ops": {op: round(ops.get(op, 0.0), 1)
                               for op in held},
                "matmul_ops_with_reduce": sorted(
                    op for op, r in held.items() if r),
                "matmul_us": round(matmul, 1),
                "other_us": round(sum(ops.values()) - matmul, 1),
                "share_of_peak": (round(flops / PEAK_FLOPS / (matmul * 1e-6),
                                        4) if matmul else None),
                "other_ops": {op: round(us, 1) for op, us in sorted(
                    ops.items(), key=lambda kv: -kv[1])
                    if op not in held and us >= 50.0},
            }
            text = json.dumps(line)
            print(text, flush=True)
            if sink:
                sink.write(text + "\n")
                sink.flush()
        del args
    return 0


if __name__ == "__main__":
    sys.exit(main())
