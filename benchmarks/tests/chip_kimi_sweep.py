#!/usr/bin/env python3
"""By hand, on the chip: the rate sweep of an open-loop cell (PR 26's
method: 90 s a rate, queue depth every 100 ms, p50 by thirds).  Written
for ``serve-latent-reason-open`` (PR 37, hence the file's name, which
``.claude/skills/verify`` and the traffic files cite); since PR 40 any
open-loop cell, ``--cell``.

    python3 benchmarks/tests/chip_kimi_sweep.py [--cell NAME] \\
        --rates 1.0,1.5,2.0,2.5 [--seconds 90] [--seed N]   # one process a rate

A rate of the sweep is the cell's traffic file with ``rate_per_s``
replaced and nothing else: the generator makes N = rate x seconds
requests from the file's distributions (a ``mixture`` of prompts its
N stratified quantiles, ``generators/_quantiles.mixture``).

This process never imports JAX: each rate runs in a child, which holds
the chip alone.  A child (``--one``) runs the cell's own runner at the
rate and prints one JSON line.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "serve-latent-reason-open"


def thirds(values: list) -> list[float]:
    n = len(values)
    return [round(sum(values[i * n // 3:(i + 1) * n // 3])
                  / max(1, (i + 1) * n // 3 - i * n // 3), 3)
            for i in range(3)]


def load_cell(name: str):
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH)
    import run as bench
    cell = next(w for w in bench.load_spec()["workloads"]
                if w["name"] == name)
    return (bench, cell, bench.load_json(bench.CONFIG_DIR, cell["config"]),
            bench.load_json(bench.TRAFFIC_DIR, cell["traffic"]))


def one(name: str, rate: float, seconds: float, seed: int) -> int:
    t_start = time.monotonic()
    bench, cell, conf, traffic = load_cell(name)
    import stats as bstats

    n = max(1, int(round(rate * seconds)))
    traffic["rate_per_s"] = rate
    bench.check_devices(cell["chips"])
    bench.compile_cache()
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=0)
    runner = importlib.import_module(f"runners.{conf['run']['kind']}")
    res = runner.run(cell, conf, traffic, args, t_start)
    c, recs = res["counters"], res["records"]
    done = sorted((r for r in recs if r.get("ok")), key=lambda r: r["t_due"])
    lat = [r["t_done"] - r["t_due"] for r in done]
    print(json.dumps({
        "rate": rate, "n": n, "correct": res["correct"],
        "failed": res["failed"], "attempted": res["attempted"],
        "tokens_per_s": round(res["end_to_end"]["serve_tokens_per_s"], 1),
        "offered_tokens_per_s": round(sum(
            r["max_new"] for r in recs) / seconds, 1),
        "tick_ms": round(1e3 * c["tick_s"] / max(1, c["ticks"]), 2),
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
    p.add_argument("--cell", default=CELL)
    p.add_argument("--rates", default="")
    p.add_argument("--seconds", type=float, default=90.0)
    p.add_argument("--seed", type=int, default=2147483659)
    p.add_argument("--one", type=float, default=0.0)
    a = p.parse_args()
    if a.one:
        return one(a.cell, a.one, a.seconds, a.seed)
    for i, rate in enumerate(float(r) for r in a.rates.split(",") if r):
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--cell", a.cell,
             "--one", str(rate), "--seconds", str(a.seconds),
             "--seed", str(a.seed + 101 * i)],
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
