"""The one-token read of the gated GQA layer's K and V rows at 8 KV
heads and 32k-114k rows a slot (the Pallas kernel ``decode_attend`` of
every decode token step) against its roofline: the greater of its FLOPs
over the bf16 peak and its live rows' bytes over the HBM peak
(archs/<arch>.long_attend_min: each live position's key and value rows
read once for the 8 query heads of their group, 4 KiB a position a
layer, 8 FLOPs a byte: memory bound), over the kernel's device time in
the traced span.

Positions and calls are COUNTED in the span: the runner reads the
engine's cumulative ``decode_kv_tokens_live`` (one layer's worth) and
``ssm_state_steps`` (live slots' token steps x the state layers) just
inside the trace's two edges (``trace_span_counters``).  The kernel
fetches whole blocks of 256 rows, so this reads under 100 by the last
block's rest: under 0.4% at 70k rows.  A program without the kernel or
the counters (the parent commit; the einsum path) reports nothing."""
import importlib
import re

KERNEL = re.compile(r"^decode[-_]attend", re.I)


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if not tr or not span or not span.get("decode_kv_tokens_live"):
        return None
    secs = sum(s for n, s in tr["ops"].items() if KERNEL.search(n))
    if secs <= 0:
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    if not hasattr(arch, "long_attend_min"):
        return None
    layers = arch.gqa_layers(conf)
    calls = (span.get("ssm_state_steps", 0) * layers
             / max(1, arch.kda_layers(conf)))
    flops, nbytes = arch.long_attend_min(
        conf, span["decode_kv_tokens_live"] * layers, calls)
    least = max(flops / ctx["peak"]["bf16_flops_per_s"],
                nbytes / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
