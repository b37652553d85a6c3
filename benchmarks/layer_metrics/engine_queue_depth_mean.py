"""Mean of stats()['queue_depth'] sampled every 100 ms in the window."""


def read(ctx):
    xs = ctx["counters"].get("queue_depth_samples")
    return sum(xs) / len(xs) if xs else None
