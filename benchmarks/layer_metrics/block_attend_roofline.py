"""The attention of the pass programs (the Pallas kernels
``block_append`` and ``block_attend``: a slot's block of L rows written
at its index, then L x G query rows a KV head against the slot's rows
up to its length) against its roofline: the bytes it must move
(archs/<arch>.block_attend_bytes: each live (slot, pass) pair's rows
once, keys and values, the block's rows written, q read and the result
written, in every layer) over the HBM peak, against the two kernels'
device time in the traced span.  Memory bound: a pair of 300 rows does
2 x 32 x 4 x 300 x 128 x 2 FLOPs against 0.6 MB a layer.

The pairs and their rows are COUNTED in the span
(``trace_span_counters``, runners/serve_blockdiff.py):
``blockdiff_slot_passes`` and ``decode_kv_tokens_live``.  The kernel
fetches whole attend blocks of 256 rows and the append rewrites a whole
tile of each slab, so this reads well under 100.  None without the
counters (the parent commit) or the kernels (off the chip)."""
import importlib
import re

KERNEL = re.compile(r"^block[-_](attend|append)", re.I)


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if not tr or not span or not span.get("blockdiff_slot_passes"):
        return None
    secs = sum(s for n, s in tr["ops"].items() if KERNEL.search(n))
    if secs <= 0:
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    need = arch.block_attend_bytes(conf, span["blockdiff_slot_passes"],
                                   span["decode_kv_tokens_live"])
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / secs
