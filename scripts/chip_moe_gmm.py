"""On the chip: the decode step's expert FFN alone, at the three served
configurations' decode shapes: the three ``jax.lax.ragged_dot``s of
``ops/moe.dropless_experts`` against the ``moe_decode_gmm`` kernel, on
the same sorted rows and group sizes, drawn as the cells draw them
(``live`` slots of ``slots`` each pick ``top_k`` distinct experts of a
``router`` wide router; the pairs on experts ``0 .. held-1`` are
computed, free slots and absent experts sort past every group).

    python scripts/chip_moe_gmm.py [--seed N] [--iters N] [--trace DIR]
    python scripts/chip_moe_gmm.py --chunk [--only NAME] [--trace DIR]

One line a shape and draw: the milliseconds a layer call of each path
takes (dispatched back to back, one ``block_until_ready`` at the end),
the touched experts, their bytes over the time as a share of the HBM
peak, and the largest difference between the two outputs.  With
``--trace`` each path's calls are also captured by the profiler and the
line carries ``*_ops_us``: the device microseconds a call of every op
takes, the kernels among them and the small XLA ops that build their
plans.  Exits 2 without a TPU: a CPU timing of either path says nothing.

``--chunk``: the four held configurations' CHUNK shapes instead (a
multi-token call of ``T`` tokens: ``T * top_k`` sorted pairs of which
the held experts own the front ``live``), and granite's buckets of 32
and 64 tokens, where half the rows are live and all of them fit.  One
line a (live rows, rows handed in): the three ``ragged_dot``s over the
rows handed in, and the ``moe_prefix_gmm`` kernel over the bound
``ops/moe.prefix_rows`` gives the shape (``bound``; where the rule says
None, the bound it would give without its margin, so that the margin
can be judged: ``prefix_rows`` is then null on the line), each as the
device microseconds of its ops from the profiler's op line.  Whether
``ragged_dot``'s time follows the rows handed in or the live ones is
what the first lines settle.  The traces go under ``--trace``, or a
fresh directory under the system's temporary one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from edl_tpu.ops import moe

HBM_BYTES_PER_S = 819e9      # one v5e chip (benchmarks/peaks.json)

# name: slots, top_k, router width, experts held, M, H, live slots drawn
SHAPES = {
    "granite-4.0-h-small": (32, 10, 72, 36, 4096, 768, (10, 20, 32)),
    "olmoe-1b-7b": (12, 8, 64, 64, 2048, 1024, (1, 2, 7)),
    "k-exaone-236b": (12, 8, 128, 16, 6144, 2048, (2, 4, 12)),
}


# name: tokens a chunk, top_k, router width, experts held, M, H
CHUNKS = {
    "openpangu-ultra-moe-718b": (512, 8, 256, 16, 7680, 2048),
    "k-exaone-236b": (256, 8, 128, 16, 6144, 2048),
    "kimi-linear-48b": (256, 8, 256, 64, 2304, 1024),
    "granite-4.0-h-small": (256, 10, 72, 36, 4096, 768),
    "granite-4.0-h-small.bucket32": (32, 10, 72, 36, 4096, 768),
    "granite-4.0-h-small.bucket64": (64, 10, 72, 36, 4096, 768),
}


def draw(rng, slots, top_k, router, held, live):
    """``(idx [slots, top_k], valid [slots])``: ``live`` slots route,
    experts at or past ``held`` become the sentinel ``held``."""
    idx = np.stack([rng.permutation(router)[:top_k] for _ in range(slots)])
    valid = np.zeros((slots,), bool)
    valid[rng.permutation(slots)[:live]] = True
    return np.minimum(idx, held).astype(np.int32), valid


def sort_rows(x, idx, valid, E):
    """What ``dropless_experts`` hands its grouped matmuls."""
    T, K = idx.shape
    flat = jnp.where(jnp.repeat(valid, K), idx.reshape(T * K), E)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
    return x[order // K], sizes


def timed(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3, out


def traced_ops(fn, args, iters, trace_dir):
    """Device microseconds a call of each op of ``fn`` takes: ``iters``
    calls under the profiler, the first device's ``XLA Ops`` line."""
    import glob

    from jax.profiler import ProfileData

    jax.block_until_ready(fn(*args))
    jax.profiler.start_trace(trace_dir)
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    ops: dict[str, float] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                ops[ev.name] = ops.get(ev.name, 0.0) + ev.duration_ns
    return {k: round(v / iters / 1e3, 2)
            for k, v in sorted(ops.items(), key=lambda kv: -kv[1])}


def weights(seed, E, M, H):
    kg, ki, ko = jax.random.split(jax.random.key(seed), 3)
    bf = jnp.bfloat16
    return ((jax.random.normal(kg, (E, M, H), bf) * M ** -0.5).astype(bf),
            (jax.random.normal(ki, (E, M, H), bf) * M ** -0.5).astype(bf),
            (jax.random.normal(ko, (E, H, M), bf) * H ** -0.5).astype(bf))


def top_ops(ops, n=6):
    return dict(list(ops.items())[:n])


def unmargined_rows(*shape):
    """The bound ``prefix_rows`` would give ``shape`` with no margin
    over the expected live rows: what fits the kernel's VMEM."""
    margin, moe._PREFIX_MARGIN = moe._PREFIX_MARGIN, 0.0
    try:
        return moe.prefix_rows(*shape)
    finally:
        moe._PREFIX_MARGIN = margin


def chunk_shapes(a, rng):
    """``--chunk``: see the module docstring."""
    trace = a.trace or tempfile.mkdtemp(prefix="chip_moe_gmm_")
    for name, (T, K, router, E, M, H) in CHUNKS.items():
        if a.only and a.only not in name:
            continue
        R = T * K
        expect = R * E // router
        shape = (T, K, E, router, M, H, jnp.bfloat16)
        Rb = moe.prefix_rows(*shape)
        bound = Rb or unmargined_rows(*shape)
        w = weights(a.seed, E, M, H)
        rows = jax.random.normal(jax.random.key(a.seed + 1), (R, M),
                                 jnp.bfloat16)
        ragged = jax.jit(moe.ragged_experts)
        kernel = jax.jit(moe.prefix_gmm)
        nbytes = 3 * E * M * H * 2
        cases = [(expect // 2, R), (expect, R), (2 * expect, R), (R, R),
                 (expect, expect), (expect, 2 * expect)]
        # the kernel's: the live rows a uniform router gives, the most a
        # router was seen to (1.16 of them), and every row of the bound
        cases += [(n, bound) for n in (expect // 2, expect,
                                       expect * 116 // 100, bound)]
        for live, handed in dict.fromkeys(
                (min(n, h), h) for n, h in cases if h <= R):
            sizes = jnp.asarray(rng.multinomial(live, [1 / E] * E), jnp.int32)
            args = (rows[:handed], sizes, *w)
            tag = f"{name}-{live}-{handed}"
            line = {"shape": name, "T": T, "rows": R, "M": M, "H": H,
                    "experts": E, "expected_live": expect, "prefix_rows": Rb,
                    "bound": bound, "live": live, "handed": handed,
                    "weights_floor_us": round(nbytes / HBM_BYTES_PER_S * 1e6,
                                              1)}
            ops = traced_ops(ragged, args, a.iters, os.path.join(
                trace, tag + "-ragged"))
            line["ragged_us"] = round(sum(ops.values()), 1)
            line["ragged_ops_us"] = top_ops(ops)
            if handed == bound:
                y_r = ragged(*args)
                y_k, fetched = kernel(*args)
                ops = traced_ops(kernel, args, a.iters, os.path.join(
                    trace, tag + "-kernel"))
                keep = np.arange(handed) < live
                line["kernel_us"] = round(sum(ops.values()), 1)
                line["kernel_ops_us"] = top_ops(ops)
                line["fetched"] = float(fetched)
                line["max_abs_diff"] = float(np.abs(
                    np.asarray(y_r, np.float32)[keep]
                    - np.asarray(y_k, np.float32)[:handed][keep]).max())
                line["max_abs"] = float(
                    np.abs(np.asarray(y_r, np.float32)[keep]).max())
            print(json.dumps(line), flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--only", default="")
    ap.add_argument("--trace", default="")
    ap.add_argument("--chunk", action="store_true")
    a = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing to time", file=sys.stderr)
        return 2
    rng = np.random.default_rng(a.seed)
    if a.chunk:
        a.iters = min(a.iters, 20)
        return chunk_shapes(a, rng)
    for name, (slots, K, router, E, M, H, lives) in SHAPES.items():
        if a.only and a.only not in name:
            continue
        x = jax.random.normal(jax.random.key(a.seed + 1), (slots, M),
                              jnp.bfloat16)
        w_gate, w_in, w_out = weights(a.seed, E, M, H)
        ragged = jax.jit(moe.ragged_experts)
        kernel = jax.jit(moe.decode_gmm)
        for live in lives:
            idx, valid = draw(rng, slots, K, router, E, live)
            rows, sizes = jax.jit(sort_rows, static_argnums=3)(
                x, jnp.asarray(idx), jnp.asarray(valid), E)
            touched = int((np.asarray(sizes) > 0).sum())
            pairs = int(np.asarray(sizes).sum())
            nbytes = touched * 3 * M * H * 2
            args = (rows, sizes, w_gate, w_in, w_out)
            t_r, y_r = timed(ragged, args, a.iters)
            t_k, (y_k, fetched) = timed(kernel, args, a.iters)
            in_group = np.arange(rows.shape[0]) < pairs
            diff = float(np.abs(
                np.asarray(y_r, np.float32)[in_group]
                - np.asarray(y_k, np.float32)[in_group]).max()) if pairs else 0
            scale = float(np.abs(np.asarray(y_r, np.float32)[in_group]).max()
                          ) if pairs else 0.0
            ops = {} if not a.trace else {
                f"{tag}_ops_us": traced_ops(
                    fn, args, 20, os.path.join(
                        a.trace, f"{name}-{live}-{tag}"))
                for tag, fn in (("ragged", ragged), ("kernel", kernel))}
            print(json.dumps({
                "shape": name, "rows": int(rows.shape[0]), "M": M, "H": H,
                "experts": E, "live": live, "pairs": pairs,
                "touched": touched, "fetched": float(fetched),
                "ragged_ms": round(t_r, 4), "kernel_ms": round(t_k, 4),
                "ragged_hbm_share": round(
                    100 * nbytes / HBM_BYTES_PER_S / (t_r * 1e-3), 2),
                "kernel_hbm_share": round(
                    100 * nbytes / HBM_BYTES_PER_S / (t_k * 1e-3), 2),
                "max_abs_diff": diff, "max_abs": scale, **ops}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
