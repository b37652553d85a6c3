"""Run by hand on the chip (PR 52's notes; not a test):
    chiprun --chips 1 --timeout 900 -- \
        python3 scripts/chip_solar_default_ladder.py 512

``solar-open2-250b-serve-ep8`` through the documented entry
(``ContinuousBatcher`` as ``runners/serve.py`` builds it, nothing
overridden) at a ``prefill_chunk`` the configuration does NOT take (the
issue's other options, 256 or 512), where ``_require_fit`` leaves the
prefill ladder more than one lane: ``warm()`` RUNS every lane count, and
a fresh prefill of two lanes x 512 tokens through this stack's
delta-rule layers side by side never returned
(``scripts/chip_kda_two_lane_prefill.py``; ``ops/kda.lanes_mapped`` is
the cure).  Prints the ladder the engine kept, the seconds ``warm()``
took, every (bucket, lanes) the prefill programs were then dispatched
at, and the tokens of two prompts served TOGETHER (one two-lane
dispatch) beside the same two served one at a time, each answer judged
as the cell judges its probe: how far the plain reference's one pass
puts each token under its best, under the nearest honest routing
(``runners/serve_hybrid.MARGIN_TOLERANCE_SIGMA``; random weights leave
near-ties, so two honest answers may part ways)."""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import numpy as np             # noqa: E402

from archs import solar_open2 as arch                      # noqa: E402
from runners import serve_hybrid as hybrid                 # noqa: E402
from edl_tpu.serving.engine import ContinuousBatcher       # noqa: E402
from edl_tpu.utils.compile_cache import enable_compile_cache   # noqa: E402

NEW = 16


def main():
    chunk = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    enable_compile_cache()
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "solar-open2-250b-serve-ep8.json")) as f:
        conf = json.load(f)
    rc = conf["run"]
    cfg = arch.transformer_config(conf, max_len=rc["max_len"], remat=False)
    params = arch.init_params(cfg, 2147485301, rc["param_dtype"],
                              split_layers=True)
    # no pool hits: the pair and the singles are all FRESH prefills
    engine = ContinuousBatcher(
        cfg, params, slots=rc["slots"], temperature=0.0, top_k=0,
        steps_per_sync=rc["steps_per_sync"], kv_block=rc["kv_block"],
        kv_pool_blocks=rc["kv_pool_blocks"], prefix_reuse=False,
        kv_max_sessions=rc["kv_max_sessions"], prefill_chunk=chunk)
    print(f"[ladder] prefill_chunk {chunk}: the engine keeps the ladder "
          f"{engine.PREFILL_KS}", flush=True)
    t = time.time()
    for bucket in (32, chunk):
        engine.warm(bucket)
    print(f"[ladder] warm() ran every lane count at buckets 32 and {chunk} "
          f"in {time.time() - t:.1f}s", flush=True)
    dispatched, fn = [], engine._prefill_fn

    def tap(P, K):
        dispatched.append((P, K))
        return fn(P, K)

    engine._prefill_fn = tap
    rng = np.random.default_rng(7)
    a, b, busy = (rng.integers(1, conf["vocab_size"], n).astype(np.int32)
                  for n in (chunk - chunk // 6, chunk - chunk // 3, 40))
    alone = [engine.submit(p, NEW).result(300).tolist() for p in (a, b)]
    # with the engine mid-decode the two land in one tick's admission
    held = engine.submit(busy, 96)
    time.sleep(0.05)
    futures = [engine.submit(p, NEW) for p in (a, b)]
    together = [f.result(300).tolist() for f in futures]
    held.result(300)
    engine.stop()
    import jax.numpy as jnp
    for how, answers in (("alone", alone), ("together", together)):
        for prompt, answer in zip((a, b), answers):
            full = jnp.asarray([prompt.tolist() + answer[:-1]], jnp.int32)
            ref = arch.reference(conf, params, full)
            worst = max(arch.tie_aware_shortfall(
                conf, params, full, ref, len(prompt) - 1 + j, token,
                limit=hybrid.MARGIN_TOLERANCE_SIGMA,
                delta=hybrid.TIE_DELTA)["shortfall"]
                for j, token in enumerate(answer))
            print(f"[ladder] {how}: a prompt of {len(prompt)} tokens, its "
                  f"{len(answer)} tokens at most {worst:.4f} sigma under the "
                  f"reference's best (limit "
                  f"{hybrid.MARGIN_TOLERANCE_SIGMA})", flush=True)
    same = sum(x == y for one, two in zip(alone, together)
               for x, y in zip(one, two))
    print(f"[ladder] prefill dispatches (bucket, lanes): {dispatched}")
    print(f"[ladder] alone    {alone}\n[ladder] together {together}")
    print(f"[ladder] {same} of {2 * NEW} tokens the same; a two-lane "
          f"dispatch ran: {(engine._bucket(len(a)), 2) in dispatched}",
          flush=True)


if __name__ == "__main__":
    main()
