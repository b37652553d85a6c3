"""Run by hand, WITHOUT a chip (PR 52's notes; not a test):
    JAX_PLATFORMS=cpu python3 benchmarks/tests/aot_solar_programs.py [chunk ...]
Compiles the decode model of ``configs/solar-open2-250b-serve-ep8.json``
ahead of time for one v5e chip from abstract shapes, as the engine's
chunk program calls it (one lane x ``chunk`` tokens against the
114,688-row slab) and as its step program does (8 lanes x 1 token), and
prints each program's argument and temporary bytes, the tile the chunk
attention runs under, whether any ``[.., P, max_len]`` float32 tensor is
in the compiled text, and the loops without a constant trip count."""
import dataclasses
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
from jax.experimental import topologies            # noqa: E402
from jax.sharding import SingleDeviceSharding      # noqa: E402

from archs import solar_open2 as arch              # noqa: E402
from edl_tpu.models.transformer import TransformerLM   # noqa: E402
from edl_tpu.ops import decode_attention as da     # noqa: E402


def main():
    with open(os.path.join(BENCH, "configs",
                           "solar-open2-250b-serve-ep8.json")) as f:
        conf = json.load(f)
    run = conf["run"]
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:1x1",
        chips_per_host_bounds=(1, 1, 1))
    one = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    # the rules that pick a kernel ask whether this is a TPU: the
    # programs are compiled FOR one, so here it is
    import importlib
    for name in ("attention", "decode_attention", "kda", "moe"):
        mod = importlib.import_module(f"edl_tpu.ops.{name}")
        if hasattr(mod, "_on_tpu"):
            mod._on_tpu = lambda: True
    cfg = dataclasses.replace(
        arch.transformer_config(conf, max_len=run["max_len"], remat=False),
        decode=True, attention_impl="dense")
    model = TransformerLM(cfg)
    T, H = run["max_len"], conf["num_attention_heads"]

    def abstract(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 and s.ndim > 1
            and s.shape[-1] != 128 else s.dtype, sharding=one), tree)

    shapes = [(1, int(a)) for a in sys.argv[1:]] or [
        (1, run["prefill_chunk"]), (run["slots"], 1)]
    for lanes, width in shapes:
        ids = jnp.zeros((lanes, width), jnp.int32)
        init = jax.eval_shape(lambda: model.init(
            jax.random.key(0), ids[:, :1], positions=ids[:, :1]))
        params = abstract(init["params"])
        cache = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), init["cache"])

        def call(params, cache, ids, idx):
            out, mut = model.apply(
                {"params": params, "cache": cache}, ids,
                positions=idx[:, None] + jnp.arange(width)[None],
                mutable=["cache", "intermediates"])
            return out[:, -1], mut["cache"]

        t = time.time()
        compiled = jax.jit(call, donate_argnums=(1,)).lower(
            params, cache,
            jax.ShapeDtypeStruct((lanes, width), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one)).compile()
        mem, text = compiled.memory_analysis(), compiled.as_text()
        loops = [ln for ln in text.splitlines()
                 if re.search(r"= .* while\(", ln)]
        print(json.dumps({
            "lanes": lanes, "width": width,
            "tiled": da.prefix_tiled(width, H, T),
            "tile_rows": da.prefix_block(lanes, width, H, T),
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "dense_scores_would_be": 4 * H * width * T,
            "whole_slab_scores_in_text": bool(re.search(
                rf"f32\[[0-9,]*{width},{T}\]", text)) if width > 1 else None,
            "loops": len(loops),
            "dynamic_loops": sum("known_trip_count" not in ln
                                 for ln in loops),
            "compile_s": round(time.time() - t, 1)}), flush=True)


if __name__ == "__main__":
    main()
