"""The window layers' one-token append and attend (the Pallas kernels
``window_append`` and ``window_attend`` of every decode token step)
against their roofline: the greater of their FLOPs over the bf16 peak
and their bytes over the HBM peak
(archs/<arch>.window_attention_min: every query head against its
window, the window's keys and values read once), over their device
time in the traced span.

The positions are COUNTED in the span: ``runners/serve_hybrid.py`` reads
the engine's cumulative ``decode_kv_tokens_window_need`` just inside
the trace's two edges (``trace_span_counters``): ``min(length, window)``
summed over the span's live (slot, token step) pairs, of one window
layer; the cost function multiplies by the window layers.  What is left
is the edge: a tick's counts reach the host at its sync, so at most one
tick of the span's 70 or so is counted on the wrong side of each edge.
The kernels fetch whole attend blocks of the ring and rewrite one tile
a slot, live or not, so this reads well under 100."""
import importlib
import re

KERNEL = re.compile(r"window[-_]a(ppend|ttend)", re.I)


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if not tr or not span or not span.get("decode_kv_tokens_window_need"):
        return None
    secs = sum(s for n, s in tr["ops"].items() if KERNEL.search(n))
    if secs <= 0:
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    flops, nbytes = arch.window_attention_min(
        conf, span["decode_kv_tokens_window_need"])
    least = max(flops / ctx["peak"]["bf16_flops_per_s"],
                nbytes / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
