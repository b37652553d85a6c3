"""The multi-token programs of an all-latent stack (the chunk lane's
``mid`` and ``fin``, the reuse and bucketed ``prefill``: every program
that runs the EXPANDED latent attention) as a share of the chip's bf16
peak: the FLOPs their tokens need (archs/<arch>.chunk_prefill_flops: 2 a
matmul weight a token with the routed experts' held share and without
the head (a call needs it for one row), every visible
(query, row) pair's score and value sum at every head, and each latent
row's expansion to ``k_nope | v`` ONCE a call a layer) over those
programs' device time in the traced span (the profiler's module line).

Tokens, pairs and rows are COUNTED in the span: ``runners/serve_mla.py``
reads the engine's cumulative ``latent_prefill_tokens``,
``latent_prefill_pairs`` and ``latent_prefill_rows_live`` just inside the
trace's two edges (``trace_span_counters``); the held share of the routed
pairs is the span's own ``moe_assignments`` over
``moe_assignments_routed``.  What the program does beyond that (rows of a
tile past the call's end, a last bucket's padding, masked pairs computed
and thrown away) is its cost and is not in the numerator.  The edge: the
host counts a call when it enqueues it, the device runs it a program or
two later.  A program without the counters (the parent commit) reports
nothing."""
import importlib
import re

PROGRAM = re.compile(r"^jit_(mid|fin|prefill)\b")


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if (not tr or not span or not span.get("latent_prefill_tokens")
            or not span.get("latent_prefill_pairs")):
        return None
    secs = sum(m["total_s"] for n, m in tr["modules"].items()
               if PROGRAM.search(n))
    if secs <= 0:
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    if not hasattr(arch, "chunk_prefill_flops"):
        return None
    routed = span.get("moe_assignments_routed")
    share = span.get("moe_assignments", 0) / routed if routed else None
    flops = arch.chunk_prefill_flops(
        conf, span["latent_prefill_tokens"], span["latent_prefill_pairs"],
        span.get("latent_prefill_rows_live", 0), share)
    return 100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / secs
