"""Changed-shard bytes the delta replicator pushed, per step of the
window (edl_delta_bytes_total differenced), in MB."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("window_steps"):
        return None
    return c["delta_bytes"] / c["window_steps"] / 1e6
