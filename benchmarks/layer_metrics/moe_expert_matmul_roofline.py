"""The grouped matmuls of the expert layers (the three projections of
every prefill and decode program: ``jax.lax.ragged_dot`` under the
scope ``moe/experts``, on the device an op named ``ragged-dot...``)
against their roofline: the greater of their FLOPs over the bf16 peak
and their bytes over the HBM peak (archs/<arch>.expert_matmul_min: the
weights of the experts each program touched once, each (token, expert)
pair's rows), over their device time in the traced span.  The kernel's
small metadata op is left out of the time.

FLOPs and bytes are COUNTED in the span: ``runners/serve_arch.py`` reads
the engine's cumulative ``moe_*`` counters just inside the trace's two
edges (``trace_span_counters``), and this takes their difference - the
pairs routed and the expert weight sets the span's own programs
touched.  Nothing is scaled from the window and nothing is modelled.
What is left is the edge: a program's counts reach the host at its
tick's sync (a chunked prefill's at its last chunk), so programs whose
ops fall inside the span may be counted outside it and the other way
round, at each edge at most one tick of about 72 and the earlier chunks
of one long prompt (PERF.md section 5 gives the size).
"""
import importlib
import re

KERNEL = re.compile(r"ragged[-_]dot(?![-_.\w]*metadata)|gmm|grouped[-_]matmul",
                    re.I)


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if not tr or not span or not span.get("moe_assignments"):
        return None
    secs = sum(s for n, s in tr["ops"].items() if KERNEL.search(n))
    if secs <= 0:
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    flops, nbytes = arch.expert_matmul_min(
        conf, span["moe_assignments"],
        span["moe_decode_experts_touched"]
        + span["moe_prefill_experts_touched"])
    least = max(flops / ctx["peak"]["bf16_flops_per_s"],
                nbytes / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
