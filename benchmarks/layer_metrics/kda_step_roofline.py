"""The delta-rule layers' one-token update (the Pallas kernel
``kda_step`` of every decode token step) against its roofline: the
greater of its FLOPs over the bf16 peak and its bytes over the HBM peak
(archs/<arch>.kda_step_min: each live (slot, token step, layer)'s 2 MiB
state read once and written once in float32, the step's own rows), over
its device time in the traced span.  Memory bound: 8 FLOPs against 8
bytes a state element.

The pairs are COUNTED in the span: ``runners/serve_latent.py`` reads the
engine's cumulative ``ssm_state_steps`` (the counter every state layer
shares) just inside the trace's two edges (``trace_span_counters``), as
``ssm_step_roofline`` counts its own.  What is left is the edge: a
tick's counts reach the host at its sync, so at most one tick of the
span is counted on the wrong side of each edge.  A program without the
kernel (the parent commit; the einsum path) reports nothing."""
import importlib
import re

KERNEL = re.compile(r"kda[-_]step", re.I)


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if not tr or not span or not span.get("ssm_state_steps"):
        return None
    secs = sum(s for n, s in tr["ops"].items() if KERNEL.search(n))
    if secs <= 0:
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    if not hasattr(arch, "kda_step_min"):
        return None
    flops, nbytes = arch.kda_step_min(conf, span["ssm_state_steps"])
    least = max(flops / ctx["peak"]["bf16_flops_per_s"],
                nbytes / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
