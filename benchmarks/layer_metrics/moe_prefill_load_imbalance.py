"""How unevenly a prefill program loaded the experts: the most any
expert received over the mean an expert received, per layer of each
prefill program (bucketed, chunked or reuse), averaged over the window
(stats()'s moe_prefill_max_load_sum over moe_prefill_groups).  1 is
even; the grouped matmul's longest group is this many times the mean."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("moe_prefill_groups"):
        return None
    return c["moe_prefill_max_load_sum"] / c["moe_prefill_groups"]
