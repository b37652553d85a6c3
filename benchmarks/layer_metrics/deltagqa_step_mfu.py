"""The whole traced span of the delta-rule + gated-GQA agent cell as a
share of the chip's bf16 peak: the model FLOPs of every program that ran
in it (archs/<arch>.step_flops for the token steps: 2 a matmul weight
with the held share of the routed experts, the delta rule's update a
live slot's token, every head's score and value sum over the rows it
saw; archs/<arch>.prefill_flops for the chunk, bucketed and reuse
prefills: the same a real token without the head, the attention's
(query, visible row) pairs, the head at the sampling rows) over the
traced WINDOW times the peak.  The step is bound by bytes (6.6 GB of
weights and 1-3 GB of K and V rows for 6 rows), so this reads low: it is
the cell's share of the whole step, beside ``sambay_step_mfu`` and
``blockdiff_step_mfu``, and the bound on what a later optimisation of
the step can claim.

Everything is COUNTED in the span (``trace_span_counters``,
``runners/serve_deltagqa.py``): live (slot, token step) pairs from
``ssm_state_steps`` over the state layers, the full layer's rows from
``decode_kv_tokens_live``, the prefills' real tokens from
``kv_prefill_tokens`` less ``kv_prefill_tokens_skipped``, their pairs
from ``kv_prefill_pairs``, their sampling rows from ``first_tokens``,
the held share from ``moe_assignments`` over ``moe_assignments_routed``.
A program without the counters (the parent commit) reports nothing."""
import importlib


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if (not tr or not span or not span.get("ssm_state_steps")
            or "kv_prefill_pairs" not in span or not tr.get("window_s")):
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    if not hasattr(arch, "step_flops"):
        return None
    routed = span.get("moe_assignments_routed")
    share = span.get("moe_assignments", 0) / routed if routed else 0.0
    steps = span["ssm_state_steps"] / arch.kda_layers(conf)
    tokens = (span.get("kv_prefill_tokens", 0)
              - span.get("kv_prefill_tokens_skipped", 0))
    flops = (arch.step_flops(conf, steps, span["decode_kv_tokens_live"],
                             share)
             + arch.prefill_flops(
                 conf, tokens, span["kv_prefill_pairs"]
                 / max(1, arch.gqa_layers(conf)),
                 span.get("first_tokens", 0), share))
    return 100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / tr["window_s"]
