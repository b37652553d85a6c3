"""Run by hand on the chip (PR 52's notes; not a test):
    chiprun --chips 1 --timeout 600 -- bash -c 'for a in "kk 2 512 dense" "kk 2 512 dense sidebyside" "gkkk 2 512" "gkkk 1 1024"; do timeout -k 5 90 python3 scripts/chip_kda_two_lane_prefill.py $a; echo rc=$?; done'

What PR 52 found: a fresh-cache prefill of TWO lanes x 512 tokens
through TWO delta-rule layers at 64 heads of 128, its lanes side by side
in one scan over chunks, never returns on a v5e chip (rc=124 under
``timeout``; 330 s were waited once), with the expert layers' kernels on
or off, with dense MLPs in their place, with every token real or all but
one padded, with the chunk's triangular solve replaced by matrix
products, and with or without the snapshot's reduction.  One such layer,
one lane of 512 or of 1,024 tokens, two lanes of 256, and 32 heads all
return in 10-40 ms; the compiled programs differ in nothing a reader of
their text can hold responsible (``/root/scratch`` AOT dumps, PR 52).
What cured it: ``ops/kda.kda_chunked`` runs a call's lanes ONE AFTER
ANOTHER where one lane's decay block reaches 128 MiB
(``ops/kda.lanes_mapped``, from the widths alone: this stack; 32 heads
keep their program), and so does splitting the heads into two groups of
32: ``kk 2 512 dense`` 20-27 ms, ``gkkk 2 512`` 36 ms against 18 ms for
one lane (my chip run, PR 52).  ``sidebyside`` below brings the old form
back, to see whether a later compiler still hangs on it.

One experiment a process: ``<plan> <lanes> <tokens> [flags]``, plan a
string of ``g`` (gated GQA) and ``k`` (delta rule) layers cut from the
configuration; flags ``sidebyside`` (the lanes in one scan, as before
the cure), ``nomoekernel``, ``dense`` (dense MLPs of width 1280),
``full`` (every token real), ``heads=N``, ``maxlen=N``."""
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402

from archs import solar_open2 as arch                      # noqa: E402
from edl_tpu.models.transformer import TransformerLM       # noqa: E402
from edl_tpu.ops import kda, moe                           # noqa: E402


def main():
    plan, K, P, flags = (sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
                         sys.argv[4:])
    if "sidebyside" in flags:
        kda._LANE_BLOCK = 1 << 62
    if "nomoekernel" in flags:
        moe._on_tpu = lambda: False
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "solar-open2-250b-serve-ep8.json")) as f:
        conf = json.load(f)
    opt = dict(f.split("=") for f in flags if "=" in f)
    cfg = arch.transformer_config(
        conf, max_len=int(opt.get("maxlen", conf["run"]["max_len"])),
        remat=False)
    kinds = tuple({"g": "global", "k": "kda"}[c] for c in plan)
    kw = dict(num_layers=len(kinds), layer_attn=kinds,
              layer_mlp=("sparse",) * len(kinds))
    if "dense" in flags:
        kw.update(layer_mlp=("dense",) * len(kinds), mlp_dim=1280)
    if "heads" in opt:
        kw["kda_heads"] = int(opt["heads"])
    cfg = dataclasses.replace(cfg, **kw)
    params = arch.init_params(cfg, 1, "bfloat16")
    jax.block_until_ready(params)
    model = TransformerLM(dataclasses.replace(cfg, decode=True,
                                              attention_impl="dense"))
    ids = jnp.zeros((K, P), jnp.int32)
    lens = jnp.full((K,), P if "full" in flags else 1, jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), ids[:, :1], positions=ids[:, :1]))["cache"]

    def prefill(params, ids, lens):
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        logits, mut = model.apply(
            {"params": params, "cache": cache}, ids,
            positions=jnp.broadcast_to(jnp.arange(P), ids.shape),
            token_mask=jnp.arange(P)[None, :] < lens[:, None],
            snap_at=jnp.zeros((K,), jnp.int32),
            mutable=["cache", "intermediates", "snap"])
        return logits[:, -1].argmax(-1), mut["cache"]

    compiled = jax.jit(prefill).lower(params, ids, lens).compile()
    print(f"[probe] {plan} lanes={K} tokens={P} {flags}: compiled, "
          f"temporaries "
          f"{compiled.memory_analysis().temp_size_in_bytes / 2**30:.2f} GiB",
          flush=True)
    for _ in range(2):
        t = time.time()
        toks, _cache = compiled(params, ids, lens)
        jax.block_until_ready(toks)
        print(f"[probe] ran in {time.time() - t:.3f}s: {toks.tolist()}",
              flush=True)
        del _cache


if __name__ == "__main__":
    main()
