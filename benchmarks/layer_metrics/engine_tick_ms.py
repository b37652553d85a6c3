"""Mean wall time of an engine tick: the tick ledger's tick_s over
ticks (ContinuousBatcher.stats()), both differenced over the window.
Host clock, measured inside the engine thread."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("ticks") or "tick_s" not in c:
        return None
    return 1e3 * c["tick_s"] / c["ticks"]
