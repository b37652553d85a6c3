"""Mean time from submit() to admission, per request admitted in the
window, as the engine stamps it: queue_wait_s_sum over admitted, both
differenced.  A mean: differenced counters are the only channel the
runner has (the tails are in edl_engine_queue_wait_seconds)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("admitted") or "queue_wait_s_sum" not in c:
        return None
    return c["queue_wait_s_sum"] / c["admitted"]
