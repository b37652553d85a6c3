"""Share of the ticks' wall time in which the engine thread was NOT
blocked on the device: (tick_s - tick_sync_s) / tick_s, differenced
over the window.  What is left of a tick when the reads of the
device's results (engine/sync) are taken out: Python, dispatches,
the pool's commits."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("tick_s") or "tick_sync_s" not in c:
        return None
    return 100.0 * (c["tick_s"] - c["tick_sync_s"]) / c["tick_s"]
