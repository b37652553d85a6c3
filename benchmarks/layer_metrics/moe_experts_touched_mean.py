"""Distinct experts one layer of one decode token step read, the mean
over the window: ContinuousBatcher.stats()'s moe_decode_experts_touched
over moe_decode_layer_steps (free slots are masked out of the routing,
so these are the live slots' experts).  With 64 experts, top-8: 12 live
slots touch about 51, 3 about 21; the step's expert bytes follow it."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("moe_decode_layer_steps"):
        return None
    return c["moe_decode_experts_touched"] / c["moe_decode_layer_steps"]
