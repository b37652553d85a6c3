#!/usr/bin/env python3
"""By hand, on the chip: the rate sweep of ``serve-latent-reason-open``
(PR 26's method: 90 s a rate, queue depth every 100 ms, p50 by thirds),
and the prompt list the traffic file keeps.

    python3 benchmarks/tests/chip_kimi_sweep.py --rates 1.0,1.5,2.0,2.5 \\
        [--seconds 90] [--seed N]          # one process a rate, in turn
    python3 benchmarks/tests/chip_kimi_sweep.py --listed 1.4   # the list

The cell's prompts are a ``listed`` distribution (the stratified
quantiles of a two-component mixture), which fixes N = rate x seconds:
every rate of the sweep gets its own list from ``mixture_quantiles``,
and the traffic file's is this function's at the chosen rate and 45 s
(``benchmarks/tests/test_serve_latent.py`` holds them equal).

This process never imports JAX: each rate runs in a child, which holds
the chip alone.  A child (``--one``) runs the cell's own runner with the
rate and the list replaced and prints one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "serve-latent-reason-open"
MIXTURE = ({"dist": "lognormal", "median": 768, "sigma": 0.8,
            "min": 128, "max": 4096},
           {"dist": "lognormal", "median": 12288, "sigma": 0.4,
            "min": 8192, "max": 24576})
SHORT_SHARE = 0.95


def mixture_quantiles(n: int) -> list[int]:
    """The n stratified quantiles of the mixture: round(0.95 n) of the
    first component, the rest of the second (the components do not
    overlap, so these are the mixture's)."""
    sys.path.insert(0, BENCH)
    from generators import _quantiles as q
    short = int(round(SHORT_SHARE * n))
    return ([int(v) for v in q.stratified(MIXTURE[0], short)]
            + [int(v) for v in q.stratified(MIXTURE[1], n - short)])


def thirds(values: list) -> list[float]:
    n = len(values)
    return [round(sum(values[i * n // 3:(i + 1) * n // 3])
                  / max(1, (i + 1) * n // 3 - i * n // 3), 3)
            for i in range(3)]


def one(rate: float, seconds: float, seed: int) -> int:
    t_start = time.monotonic()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH)
    import run as bench
    from runners import serve_latent
    import stats as bstats

    spec = bench.load_spec()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    conf = bench.load_json(bench.CONFIG_DIR, cell["config"])
    traffic = bench.load_json(bench.TRAFFIC_DIR, cell["traffic"])
    n = max(1, int(round(rate * seconds)))
    traffic["rate_per_s"] = rate
    traffic["prompt_tokens"] = {"dist": "listed",
                                "values": mixture_quantiles(n)}
    bench.check_devices(cell["chips"])
    bench.compile_cache()
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=0)
    res = serve_latent.run(cell, conf, traffic, args, t_start)
    c, recs = res["counters"], res["records"]
    done = sorted((r for r in recs if r.get("ok")), key=lambda r: r["t_due"])
    lat = [r["t_done"] - r["t_due"] for r in done]
    print(json.dumps({
        "rate": rate, "n": n, "correct": res["correct"],
        "failed": res["failed"], "attempted": res["attempted"],
        "tokens_per_s": round(res["end_to_end"]["serve_tokens_per_s"], 1),
        "p50": round(res["end_to_end"].get("serve_latency_p50_s", -1), 3),
        "p90": round(res["end_to_end"].get("serve_latency_p90_s", -1), 3),
        "queue_thirds": thirds(c["queue_depth_samples"]),
        "live_thirds": thirds(c["active_slots_samples"]),
        "p50_thirds": [round(bstats.percentile(lat[i * len(lat) // 3:
                                                   (i + 1) * len(lat) // 3]
                                               or [0], 50), 3)
                       for i in range(3)],
        "lateness_ms_max": round(1e3 * max(
            (r["t_send"] - r["t_due"] for r in recs
             if r.get("t_send") is not None), default=0.0), 2),
        "memory_peak_bytes": res["device"].get("memory_peak_bytes"),
        "setup_s": round(res["end_to_end"]["setup_s"], 1)}), flush=True)
    return 0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--rates", default="")
    p.add_argument("--seconds", type=float, default=90.0)
    p.add_argument("--seed", type=int, default=2147483659)
    p.add_argument("--one", type=float, default=0.0)
    p.add_argument("--listed", type=float, default=0.0)
    a = p.parse_args()
    if a.listed:
        print(json.dumps(mixture_quantiles(int(round(a.listed * 45.0)))))
        return 0
    if a.one:
        return one(a.one, a.seconds, a.seed)
    for i, rate in enumerate(float(r) for r in a.rates.split(",") if r):
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one", str(rate),
             "--seconds", str(a.seconds), "--seed", str(a.seed + 101 * i)],
            capture_output=True, text=True)
        tail = out.stdout.strip().splitlines()
        keep = [ln for ln in tail if ln.startswith(("{", "[bench] probe",
                                                    "[bench] window"))]
        print(f"rate {rate}: rc {out.returncode}", flush=True)
        print("\n".join(keep[-3:]) if keep else out.stderr[-2000:],
              flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
