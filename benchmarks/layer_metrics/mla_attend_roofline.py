"""The latent attention layers' one-token read at 128 heads (the Pallas
kernels ``latent_append`` and ``latent_attend`` of every decode token
step, five layers of them) against its roofline: the greater of its
FLOPs over the bf16 peak and its bytes over the HBM peak
(archs/<arch>.latent_attention_min: each live position's row ``c | k_pe``
read ONCE for all heads a (slot, token step, layer), 1,152 bytes, and
278,528 FLOPs of scores and value sum against it: 242 FLOPs a byte, at
this chip's ridge of 240, so both terms matter), over the two kernels'
device time in the traced span.

Positions and calls are COUNTED in the span: ``runners/serve_mla.py``
reads the engine's cumulative ``latent_tokens_live`` and
``latent_decode_calls`` just inside the trace's two edges
(``trace_span_counters``).  The cache keeps rows of 640 values (whole
lane tiles), the kernel fetches whole tiles of 512 positions
(``kv_latent_read_ratio``) and pads nothing else: both are the program's
cost, not the algorithm's, and are not in the numerator.  A program
without the kernels or the counters (the parent commit) reports
nothing."""
import importlib
import re

KERNEL = re.compile(r"latent[-_](append|attend)", re.I)


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if (not tr or not span or not span.get("latent_tokens_live")
            or not span.get("latent_decode_calls")):
        return None
    secs = sum(s for n, s in tr["ops"].items() if KERNEL.search(n))
    if secs <= 0:
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    if not hasattr(arch, "latent_attention_min"):
        return None
    flops, nbytes = arch.latent_attention_min(
        conf, span["latent_tokens_live"], span["latent_decode_calls"])
    least = max(flops / ctx["peak"]["bf16_flops_per_s"],
                nbytes / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
