"""The one-row attention over the ONE growing cache of a stack whose
upper layers borrow it (the Pallas kernel ``decode_attend``: the full
layer's own read and each cross layer's read of the same rows, in every
decode token step and at the sampling row of every prefill) against its
roofline: the greater of its FLOPs over the bf16 peak and its bytes over
the HBM peak (archs/<arch>.shared_kv_attend_min: every visible position's
keys and values once a read, ``kv_readers`` reads a row), over the
kernel's device time in the traced span.  Memory bound.

The rows are COUNTED in the span (``trace_span_counters``,
``runners/serve_sambay.py``): ``decode_kv_tokens_live`` (the positions
the step programs' live slots held, one layer's worth) times the
reading layers, and ``borrowed_kv_tokens_prefill`` (what the cross
layers' sampling rows needed in the prefills; the full layer's own
prefill read is the einsum path and no ``decode_attend``).  The kernel
fetches whole attend blocks, so this reads under 100; a window layer's
kernel is named ``window_attend`` and is not in it.  A program without
the counters (the parent commit) reports nothing."""
import importlib
import re

KERNEL = re.compile(r"^decode[-_]attend", re.I)


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if (not tr or not span or not span.get("decode_kv_tokens_live")
            or "borrowed_kv_tokens_prefill" not in span):
        return None
    secs = sum(s for n, s in tr["ops"].items() if KERNEL.search(n))
    if secs <= 0:
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    rows = (span["decode_kv_tokens_live"] * arch.kv_readers(conf)
            + span["borrowed_kv_tokens_prefill"])
    flops, nbytes = arch.shared_kv_attend_min(conf, rows)
    least = max(flops / ctx["peak"]["bf16_flops_per_s"],
                nbytes / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
