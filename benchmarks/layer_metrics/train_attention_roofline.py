"""The attention kernels' (splash forward and backward) device time in
the traced steps against the operations causal attention needs for the
sequences one chip processed: compute-bound at these shapes, so the
roofline is FLOPs over the bf16 peak."""
import re

import costs

KERNEL = re.compile(r"splash|flash|mha|attention", re.I)


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if not tr:
        return None
    secs = sum(s for n, s in tr["ops"].items() if KERNEL.search(n))
    steps = c.get("traced_steps", 0)
    if secs <= 0 or not steps:
        return None
    per_chip = c["batch"] // c["chips"] if c["batch"] >= c["chips"] else 1
    flops = costs.attention_train_flops(ctx["conf"], c["seq"],
                                        steps * per_chip)
    return 100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / secs
