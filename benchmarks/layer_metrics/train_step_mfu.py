"""Model FLOPs the traced steps needed (costs.train_flops_per_token x
tokens) over the device time of the step program's executions, over
the chips' bf16 peak.  Recomputed operations are not counted."""
import costs


def step_modules(trace):
    return {n: m for n, m in trace["modules"].items()
            if "step" in n and m["total_s"] > 0}


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    mods = step_modules(tr) if tr else {}
    if not mods:
        return None
    name = max(mods, key=lambda n: mods[n]["total_s"])
    runs, dev_s = mods[name]["count"], mods[name]["total_s"]
    flops = (costs.train_flops_per_token(ctx["conf"], c["seq"])
             * runs * c["batch"] * c["seq"])
    return 100.0 * flops / dev_s / c["chips"] / ctx["peak"]["bf16_flops_per_s"]
