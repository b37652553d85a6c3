#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name: the cell in
``BENCHMARK.json``, its configuration in ``benchmarks/configs/``, its
traffic in ``benchmarks/traffic/``, the traffic's generator in
``benchmarks/generators/``, the runner for the configuration's kind in
``benchmarks/runners/`` and each per-layer metric's reader in
``benchmarks/layer_metrics/``.  The last line of standard output is the
one JSON result; everything else goes on earlier lines.  Without a TPU
(or with fewer chips than the cell asks for) it exits 2 and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()      # set-up is counted from process start

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


# where the cell's files are looked up (the CPU tests of the benchmark
# point these at their toy-sized copies)
SPEC_PATH = os.path.join(ROOT, "BENCHMARK.json")
CONFIG_DIR = os.path.join(HERE, "configs")
TRAFFIC_DIR = os.path.join(HERE, "traffic")


def load_json(directory: str, name: str) -> dict:
    with open(os.path.join(directory, f"{name}.json")) as f:
        return json.load(f)


def load_spec() -> dict:
    with open(SPEC_PATH) as f:
        return json.load(f)


def check_devices(chips: int) -> None:
    """No accelerator, no result.  (The CPU tests of the benchmark
    replace this function; run.py has no switch for it.)"""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"[bench] this cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        raise SystemExit(2)


def compile_cache() -> str:
    """JAX's persistent cache at a fixed place: where
    JAX_COMPILATION_CACHE_DIR says, else ``.jax_cache`` in the checkout
    (the program's own rule, utils/compile_cache.py).  Every program is
    kept, however quick its compile: a run pays for each again."""
    import jax
    from edl_tpu.utils.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def cells_of(metric: dict, spec: dict) -> list[str]:
    return metric.get("workloads") or [w["name"] for w in spec["workloads"]]


def layer_metrics(spec: dict, cell: dict, conf: dict, traffic: dict,
                  result: dict) -> dict:
    import costs
    ctx = {"cell": cell, "conf": conf, "traffic": traffic, "spec": spec,
           "counters": result["counters"], "records": result["records"],
           "trace": result["trace"], "device": result["device"],
           "peak": costs.peaks(result["device"]["kind"])}
    out = {}
    for m in spec["per_layer"]:
        if cell["name"] not in cells_of(m, spec):
            continue
        reader = importlib.import_module(f"layer_metrics.{m['name']}")
        value = reader.read(ctx)
        if value is not None:       # nothing to read: left out of the line
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    spec = load_spec()
    cell = next((w for w in spec["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"[bench] no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    conf = load_json(CONFIG_DIR, cell["config"])
    traffic = load_json(TRAFFIC_DIR, cell["traffic"])
    check_devices(cell["chips"])
    print(f"[bench] {cell['name']}: config {cell['config']}, traffic "
          f"{cell['traffic']}, seed {args.seed}, {args.seconds}s, trace "
          f"{args.trace}; compile cache {compile_cache()}", flush=True)
    runner = importlib.import_module(f"runners.{conf['run']['kind']}")
    result = runner.run(cell, conf, traffic, args, T_START)

    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "device": result["device"]}
    if args.trace:
        import trace_reduce
        tr = result["trace"]
        line["metrics"] = layer_metrics(spec, cell, conf, traffic, result)
        line["device"]["busy_s"] = tr["busy_s"]
        line["device"]["window_s"] = tr["window_s"]
        line["breakdown"] = trace_reduce.breakdown(tr)
    else:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        want = [m["name"] for m in spec["end_to_end"]
                if cell["name"] in cells_of(m, spec)]
        missing = [n for n in want if n not in result["end_to_end"]]
        if missing:
            print(f"[bench] no value for {missing}: too few answered "
                  f"requests", file=sys.stderr)
            return 1
        line["metrics"] = {n: {"value": float(result["end_to_end"][n]),
                               "unit": units[n]} for n in want}
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # every child has been stopped and waited for; what is left are the
    # program's daemon threads, which must not hold the exit
    os._exit(rc)
