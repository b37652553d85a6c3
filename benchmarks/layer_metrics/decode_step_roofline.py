"""The bytes a decode step must read (every matmul weight once and the
live keys and values: costs.decode_step_min_bytes) over the HBM peak,
against the traced time of the decode program per token step.  Memory
bound: a step of 12 lanes does 2 x 1.3 G x 12 FLOPs against 3 GB."""
import costs


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if not tr:
        return None
    mods = {n: m for n, m in tr["modules"].items() if "step" in n}
    if not mods:
        return None
    m = mods[max(mods, key=lambda n: mods[n]["total_s"])]
    per_token_step = m["total_s"] / m["count"] / c["steps_per_sync"]
    active = c.get("active_slots_samples") or [0]
    live = (sum(active) / len(active)) * c.get("mean_context_tokens", 0.0)
    need = costs.decode_step_min_bytes(ctx["conf"], live)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / per_token_step
