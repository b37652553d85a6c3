"""The bytes a decode token step of an expert model must read
(attention, router and head weights once; the experts the live slots
touched, a layer's mean, in every layer; the live keys and values:
archs/<arch>.decode_step_min_bytes) over the HBM peak, against the
traced time of the decode program per token step.  Memory bound: a step
of 12 lanes does 12 x 2 x 0.5 G FLOPs against 3 GB.

The experts touched are counted for the step programs of the traced
span (``trace_span_counters``: the engine's counters read at the
trace's edges by runners/serve_arch.py).  The live keys and values, a
tenth of the bytes, are the window's: mean live slots of the 100 ms
samples x the mean context of its requests."""
import importlib


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    span = c.get("trace_span_counters")
    if not tr or not span or not span.get("moe_decode_layer_steps"):
        return None
    mods = {n: m for n, m in tr["modules"].items() if "step" in n}
    if not mods:
        return None
    arch = importlib.import_module(f"archs.{ctx['conf']['run']['arch']}")
    m = mods[max(mods, key=lambda n: mods[n]["total_s"])]
    per_token_step = m["total_s"] / m["count"] / c["steps_per_sync"]
    active = c.get("active_slots_samples") or [0]
    live = (sum(active) / len(active)) * c.get("mean_context_tokens", 0.0)
    touched = (span["moe_decode_experts_touched"]
               / span["moe_decode_layer_steps"])
    need = arch.decode_step_min_bytes(ctx["conf"], touched, live)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / per_token_step
