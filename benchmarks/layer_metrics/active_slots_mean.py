"""Mean of stats()['active_slots'] sampled every 100 ms in the window."""


def read(ctx):
    xs = ctx["counters"].get("active_slots_samples")
    return sum(xs) / len(xs) if xs else None
