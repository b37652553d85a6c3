"""The Mamba-1 layers' one-token update (the Pallas kernel
``mamba1_step`` of every decode token step) against its roofline: the
greater of its FLOPs over the bf16 peak and its bytes over the HBM peak
(archs/<arch>.ssm_step_min: each live (slot, token step, layer)'s state
read once and written once in float32, the step's own rows), over its
device time in the traced span.  Memory bound: 6 FLOPs against 8 bytes
a state element.

The pairs are COUNTED in the span, as ``ssm_step_roofline`` counts
them: ``runners/serve_sambay.py`` reads the engine's cumulative
``ssm_state_steps`` (any recurrent layer's) just inside the trace's two
edges (``trace_span_counters``).  A state of 16 x 5120 float32 is a
third of a megabyte: a slot's grid step moves little against its fixed
cost.  NOT LISTED in ``BENCHMARK.json`` yet (PR 45): on the chip it
read 125-138%, a live slot's 717 KB moved in 0.70 us where the published
819 GB/s allow 0.875, and until ``scripts/chip_mamba1_step.py`` says what
the kernel's time leaves out a share over 100 would refuse every PR that
reports it (PERF.md section 7).  A program without the kernel (the
parent commit; the einsum path) reports nothing."""
import importlib
import re

KERNEL = re.compile(r"mamba1[-_]step", re.I)


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if not tr or not span or not span.get("ssm_state_steps"):
        return None
    secs = sum(s for n, s in tr["ops"].items() if KERNEL.search(n))
    if secs <= 0:
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    flops, nbytes = arch.ssm_step_min(conf, span["ssm_state_steps"])
    least = max(flops / ctx["peak"]["bf16_flops_per_s"],
                nbytes / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
