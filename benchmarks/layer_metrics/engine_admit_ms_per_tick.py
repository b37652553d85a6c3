"""Host time a tick spends admitting (engine/admit: queue drain,
prefix matching, prefill and chunk dispatches): tick_admit_s over
ticks, both differenced over the window."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("ticks") or "tick_admit_s" not in c:
        return None
    return 1e3 * c["tick_admit_s"] / c["ticks"]
