"""The bytes one PASS of an expert model that generates by blocks must
read (attention, router and head weights once; the experts the live
slots' L positions touched, a layer's mean, in every layer; the rows the
live slots hold: archs/<arch>.decode_step_min_bytes, for a pass) over
the HBM peak, against the traced time of the pass program a pass.
Memory bound: a pass of 10 live slots does 40 x 2 x 0.45 G FLOPs against
7 GB.

The experts touched, the live pairs and their rows are counted for the
pass programs of the traced span (``trace_span_counters``, the engine's
counters read at the trace's edges by runners/serve_blockdiff.py):
``moe_decode_experts_touched`` over ``moe_decode_layer_steps`` (a layer
call of a pass), ``decode_kv_tokens_live`` over ``blockdiff_passes`` (the
rows all live slots held, a pass).  The program is found by its name
(``_pass_impl``); a dispatch is ``steps_per_sync`` passes.  None without
the counters (the parent commit) or the program."""
import importlib


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    span = c.get("trace_span_counters")
    if (not tr or not span or not span.get("moe_decode_layer_steps")
            or not span.get("blockdiff_passes")):
        return None
    mods = {n: m for n, m in tr["modules"].items() if "pass_impl" in n}
    if not mods:
        return None
    arch = importlib.import_module(f"archs.{ctx['conf']['run']['arch']}")
    m = mods[max(mods, key=lambda n: mods[n]["total_s"])]
    per_pass = m["total_s"] / m["count"] / c["steps_per_sync"]
    touched = (span["moe_decode_experts_touched"]
               / span["moe_decode_layer_steps"])
    rows = span["decode_kv_tokens_live"] / span["blockdiff_passes"]
    need = arch.decode_step_min_bytes(ctx["conf"], touched, rows)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / per_pass
