"""The whole traced span of a decoder-hybrid-decoder cell as a share of
the chip's bf16 peak: the model FLOPs of every program that ran in it
(archs/<arch>.step_flops for the token steps: 2 a matmul weight and the
recurrence a live slot's token, attention over the rows it saw;
archs/<arch>.prefill_flops for the multi-token programs under the
last-position cut: the layers below the tail at every real token, the
tail and the head at the sampling rows alone) over the device's BUSY
time in the span times the peak.  The step is bound by the weights'
bytes, not by FLOPs (a step of 32 slots multiplies 7.7 GB of weights by
32 rows), so this reads low: it is the cell's share of the whole step,
the bound on what a later optimisation of the step can claim.

Everything is COUNTED in the span (``trace_span_counters``,
``runners/serve_sambay.py``): live (slot, token step) pairs from
``ssm_state_steps`` over the recurrent layers, the full layer's rows
from ``decode_kv_tokens_live`` and the windows' from
``decode_kv_tokens_window_need``, the prefills' real tokens and sampling
rows from ``prefill_layer_visits`` and ``prefill_layer_visits_cut``.  The
prefills' own attention (under 1% of their FLOPs at these lengths) is
not counted: the share reads that much low, never high.  A program
without the counters (the parent commit) reports nothing."""
import importlib


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if (not tr or not span or not span.get("ssm_state_steps")
            or "prefill_layer_visits_cut" not in span
            or not tr.get("busy_s")):
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    kinds = arch.layer_kinds(conf)
    n = len(kinds)
    below = sum(k not in ("gmu", "cross") for k in kinds)
    steps = span["ssm_state_steps"] / arch.mamba_layers(conf)
    flops = arch.step_flops(
        conf, steps, span["decode_kv_tokens_live"] * arch.kv_readers(conf),
        span.get("decode_kv_tokens_window_need", 0)
        * arch.window_layers(conf))
    ran, cut = span["prefill_layer_visits"], span["prefill_layer_visits_cut"]
    real = (ran + cut) / n
    flops += arch.prefill_flops(conf, real, (ran - real * below) / (n - below),
                                0.0, 0.0)
    return 100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / tr["busy_s"]
