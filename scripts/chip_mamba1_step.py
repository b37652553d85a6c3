"""On the chip: the Mamba-1 one-token update alone at the served shape
(32 slots, inner 5120, state 16): the Pallas kernel ``mamba1_step``
against its plain ``jax.numpy`` reference, on the same states.

    python scripts/chip_mamba1_step.py [--iters N] [--live 8,16,32]

One JSON line a number of live slots: the device microseconds a call of
each path takes from the profiler's op line (trust these, not the wall
clock), the kernel's bytes over its time as a share of the HBM peak
(``benchmarks/archs/phi4flash.ssm_step_min``'s count: a live slot's
state read once and written once, the step's rows), the wall-clock
microseconds of calls dispatched back to back, and the largest
difference between the two outputs.  Exits 2 without a TPU.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from edl_tpu.ops import mamba1

HBM_BYTES_PER_S = 819e9      # one v5e chip (benchmarks/peaks.json)
B, N, DI = 32, 16, 5120


def op_us(fn, args, iters, trace_dir):
    """``{op name: device microseconds a call}`` of ``fn``'s ops."""
    from jax.profiler import ProfileData
    state, rest = args[0], args[1:]
    _, state = fn(state, *rest)             # the state is donated
    jax.block_until_ready(state)
    jax.profiler.start_trace(trace_dir)
    for _ in range(iters):
        _, state = fn(state, *rest)
    jax.block_until_ready(state)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = ev.name.split(" = ")[0].lstrip("%")
                out[name] = out.get(name, 0.0) + ev.duration_ns * 1e-3 / iters
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--iters", type=int, default=200)
    p.add_argument("--live", default="8,16,32")
    a = p.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("this measurement needs the chip", file=sys.stderr)
        return 2
    ks = jax.random.split(jax.random.key(0), 6)
    state = jax.random.normal(ks[0], (B, N, DI))
    x = jax.random.normal(ks[1], (B, DI)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, DI)))
    A = -jnp.exp(jax.random.normal(ks[3], (N, DI)))
    bv = jax.random.normal(ks[4], (B, N)).astype(jnp.bfloat16)
    cv = jax.random.normal(ks[5], (B, N)).astype(jnp.bfloat16)
    kernel = jax.jit(mamba1.mamba1_step, donate_argnums=(0,))
    plain = jax.jit(mamba1.mamba1_step_reference, donate_argnums=(0,))
    for n in (int(v) for v in a.live.split(",")):
        live = jnp.arange(B) < n
        args = (x, dt, A, bv, cv, live)
        y0, s0 = plain(state + 0.0, *args)
        y1, s1 = kernel(state + 0.0, *args)
        diff = float(max(jnp.abs(y0 - y1).max(), jnp.abs(s0 - s1).max()))
        line = {"live": n, "max_diff": diff}
        for name, fn in (("kernel", kernel), ("plain", plain)):
            with tempfile.TemporaryDirectory() as d:
                ops = op_us(fn, (state + 0.0, *args), a.iters, d)
            _, s = fn(state + 0.0, *args)
            jax.block_until_ready(s)
            t0 = time.perf_counter()
            for _ in range(a.iters):
                _, s = fn(s, *args)
            jax.block_until_ready(s)
            line[f"{name}_wall_us"] = round(
                1e6 * (time.perf_counter() - t0) / a.iters, 2)
            line[f"{name}_ops_us"] = {k: round(v, 2) for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:5]}
        need = n * (2 * 4 * N * DI + 4 * (3 * DI + 2 * N))
        step = sum(v for k, v in line["kernel_ops_us"].items()
                   if "mamba1_step" in k)
        line["kernel_hbm_share"] = (round(need / HBM_BYTES_PER_S / (
            step * 1e-6), 3) if step else None)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
