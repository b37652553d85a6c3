"""The whole traced span of a cell that generates by diffusion over
blocks as a share of the chip's bf16 peak: the model FLOPs of every
program that ran in it (archs/<arch>.pass_flops for the live (slot,
pass) pairs and the rows they saw: 2 a matmul weight a position, the
head included, and attention over the rows; archs/<arch>.prefill_flops
for the real prompt tokens prefilled: no head) over the device's BUSY
time in the span times the peak, as ``sambay_step_mfu`` is built.  A
pass is bound by the weights' bytes (10 live slots multiply 7 GB of
weights by 40 rows), so this reads low: it is the cell's share of the
whole step, the bound on what a later optimisation may claim here.  It
counts only work that was done (a dead pass of a finished slot, a padded
prompt row and the prefills' own attention are left out), so it can
read short and never over.

Everything is COUNTED in the span (``trace_span_counters``,
runners/serve_blockdiff.py): ``blockdiff_slot_passes``,
``decode_kv_tokens_live`` and the prompt tokens prefilled
(``kv_prefill_tokens`` less ``kv_prefill_tokens_skipped``).  A program
without the counters (the parent commit) reports nothing."""
import importlib


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if (not tr or not span or not span.get("blockdiff_slot_passes")
            or not tr.get("busy_s")):
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    flops = arch.pass_flops(conf, span["blockdiff_slot_passes"],
                            span["decode_kv_tokens_live"])
    flops += arch.prefill_flops(
        conf, max(0, span.get("kv_prefill_tokens", 0)
                  - span.get("kv_prefill_tokens_skipped", 0)))
    return 100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / tr["busy_s"]
