"""The latent attention layers' one-token read (the Pallas kernels
``latent_append`` and ``latent_attend`` of every decode token step)
against its roofline: the greater of its FLOPs over the bf16 peak and
its bytes over the HBM peak (archs/<arch>.latent_attention_min: each
live position's row ``c | k_pe`` read ONCE for all heads a (slot, token
step, layer), 1,152 bytes; the call's own row and queries), over the two
kernels' device time in the traced span.  60 FLOPs a byte: memory bound
on this chip.

The positions are COUNTED in the span: ``runners/serve_latent.py`` reads
the engine's cumulative ``latent_tokens_live`` just inside the trace's
two edges (``trace_span_counters``); the calls are the live slots'
token steps a latent layer, from ``ssm_state_steps`` over the state
layers a latent layer.  The cache keeps rows of 640 values (whole lane
tiles) and the kernel fetches whole tiles of 512 positions
(``kv_latent_read_ratio``): both are the program's cost, not the
algorithm's, and are not in the numerator.  A program without the
kernels reports nothing."""
import importlib
import re

KERNEL = re.compile(r"latent[-_](append|attend)", re.I)


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if not tr or not span or not span.get("latent_tokens_live"):
        return None
    secs = sum(s for n, s in tr["ops"].items() if KERNEL.search(n))
    if secs <= 0:
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    if not hasattr(arch, "latent_attention_min"):
        return None
    calls = (span.get("ssm_state_steps", 0) * arch.latent_layers(conf)
             / max(1, arch.kda_layers(conf)))
    flops, nbytes = arch.latent_attention_min(
        conf, span["latent_tokens_live"], calls)
    least = max(flops / ctx["peak"]["bf16_flops_per_s"],
                nbytes / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
