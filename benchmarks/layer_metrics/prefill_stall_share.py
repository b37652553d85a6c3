"""The engine's prefill_stall_s (host time dispatching prefill work
while decode lanes were live) differenced over the window, over the
window."""


def read(ctx):
    c = ctx["counters"]
    if "prefill_stall_s" not in c:
        return None
    return 100.0 * c["prefill_stall_s"] / c["window_s"]
