"""Host time a tick spends handing tokens to slots and futures and
committing finished lanes to the pool (engine/finish + the
engine/kv_commit nested in it): (tick_finish_s + tick_kv_commit_s)
over ticks, differenced over the window."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("ticks") or "tick_finish_s" not in c:
        return None
    return 1e3 * (c["tick_finish_s"] + c.get("tick_kv_commit_s", 0.0)) \
        / c["ticks"]
