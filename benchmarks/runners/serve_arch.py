"""A serving cell of an architecture ``model.py`` and ``reference.py``
do not know: the configuration's ``run.arch`` names a module under
``archs/`` that is both (``transformer_config``, ``init_params``,
``reference``).  ``runners/serve.py`` imports ``model`` and
``reference`` by name when it is called, so this runner registers the
module under both names, states its tolerances, and hands over.  One
process runs one cell: no other cell sees the registration.

What it adds to ``runners/serve.py``'s ``correct`` (``expert_checks``):
the served-token margin cannot see an expert layer that computes in a
lower precision or drops an assignment (PERF.md section 6, PR 26), so
the cell also holds the program's block to the reference at the level
of logits, and the engine's expert counters to what the host routed.
And in a traced run it reads the engine's counters at the trace's own
edges, so that the expert readers count what the traced span held.
"""

from __future__ import annotations

import importlib
import sys

import numpy as np

from . import common, serve

# The served path's greedy tokens against the float32 reference, as
# ``serve.MARGIN_TOLERANCE_SIGMA`` defines it (the shortfall of the
# served token under the reference's best logit, in standard deviations
# of the reference's logits at that position).  With experts there is
# one more honest way to differ than bf16 rounding of a near-tied
# argmax: bf16 activations can swap the 8th and 9th expert of a token
# whose router probabilities nearly tie, which replaces one of eight
# expert outputs by another of nearly the same weight.  Measured on the
# chip at published widths (PERF.md section 6, PR 26): 3.6-6.3% of
# (token, layer) pairs choose another expert set than the reference,
# and the largest shortfall over 25 runs of the cell and 16 more probes
# is 0.048 sigma.  A renormalised gate reads 0.41-0.79 on every one of
# 8 probes (27-33 of 64 tokens argmax-equal against 61-64).  Dropped
# assignments (capacity 1 x) read up to 0.075 and int8 experts up to
# 0.018: this margin does not see those two, the checks below do.
MARGIN_TOLERANCE_SIGMA = 0.15

# The program's expert layers ALONE against the reference's, each fed
# the reference's own input to it (archs/<arch>.block_agreement's
# ``expert_error``: the norm of program - reference over the norm of
# the reference's output, the median over the probe's tokens and the
# layers).  The limit lies between two readings on the chip at
# published widths (PERF.md section 6, PR 26, chip run 7): the largest
# the program gives, 0.00512 (0.00509-0.00512 over 20 readings of 13
# seeds: one layer of bf16 rounding, the same in every run), and what
# the REFERENCE's own expert layers give in float32 arithmetic with the
# expert weights rounded to int8 per output channel, the nearest
# precision below the stated bfloat16: 0.00961-0.00964, not correct.
# The program with int8 experts reads 0.01089-0.01092, dropped
# assignments (capacity 1 x) 0.275-0.323, a renormalised gate 1.31-1.40.
EXPERT_TOLERANCE = 0.007

# The whole block at the level of logits (``logit_error_sigma``: at each
# position of the probe the root mean square over the vocabulary of
# program - reference, in standard deviations of the reference's
# logits; the median over the positions).  Six layers of bf16 rounding,
# attention included: the program reads 0.0076-0.0104 sigma over the
# same 20 readings, and int8 experts are lost in that (0.0079-0.0105:
# what the measure above is for).  It holds the rest of the block:
# dropped assignments read 0.060-0.072, a renormalised gate 0.267-0.275.
# 0.025 is 2.4 times the largest honest reading and under half the
# smallest wrong one.
BLOCK_TOLERANCE_SIGMA = 0.025


class _Reference:
    """What ``runners/serve.py`` sees as ``reference``: the arch
    module's plain reference, which also keeps how the program's block
    compared on the same probe (``block``)."""

    def __init__(self, arch):
        self.arch, self.block = arch, None

    def logits(self, conf, params, ids):
        ref = self.arch.reference(conf, params, ids)
        self.block = self.arch.block_agreement(conf, params, ids, ref)
        return ref["logits"]


class _Spans(serve._Spans):
    """``runners/serve.py`` hands its engine to ``tap_engine`` once:
    the one place this runner can meet it."""

    def tap_engine(self, engine) -> None:
        super().tap_engine(engine)
        _TraceWindow.engine = engine


class _TraceWindow(common.TraceWindow):
    """The traced stretch, with the engine's cumulative counters read
    just inside its two edges."""

    engine = None
    edges: list[dict] = []

    def start(self) -> None:
        super().start()
        self.edges.append(self.engine.stats())

    def stop(self) -> None:
        self.edges.append(self.engine.stats())
        super().stop()


def expert_checks(conf: dict, counters: dict, block: dict | None) -> dict:
    """What ``correct`` also rests on in an expert cell, from the
    window's counters and the probe's ``block_agreement``."""
    pairs = (conf["num_experts_per_tok"] * conf["num_hidden_layers"]
             * counters.get("moe_tokens", 0))
    return {
        # exact: the engine's expert layers dropped nothing ...
        "nothing_dropped": counters.get("moe_prefill_drops", -1) == 0,
        # ... and routed top_k pairs in every layer for every real token
        # the host sent through them (prompt tokens prefilled, live
        # slots x token steps: ContinuousBatcher.stats()'s moe_tokens)
        "every_token_routed": pairs > 0 and
        counters.get("moe_assignments", -1) == pairs,
        "expert_layers": block is not None and bool(
            np.median(block["expert_error"]) <= EXPERT_TOLERANCE),
        "block_logits": block is not None and bool(
            np.median(block["logit_error_sigma"]) <= BLOCK_TOLERANCE_SIGMA),
    }


def run(cell: dict, conf: dict, traffic: dict, args, t_start: float) -> dict:
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    ref = _Reference(arch)
    sys.modules["model"], sys.modules["reference"] = arch, ref
    serve.MARGIN_TOLERANCE_SIGMA = MARGIN_TOLERANCE_SIGMA
    serve._Spans, common.TraceWindow = _Spans, _TraceWindow
    result = serve.run(cell, conf, traffic, args, t_start)
    counters = result["counters"]
    checks = expert_checks(conf, counters, ref.block)
    print(f"[bench] expert checks {checks}: {counters.get('moe_assignments')}"
          f" assignments for {counters.get('moe_tokens')} tokens, "
          f"{counters.get('moe_prefill_drops')} drops; tolerances: expert "
          f"layers {EXPERT_TOLERANCE}, block logits {BLOCK_TOLERANCE_SIGMA} "
          f"sigma", flush=True)
    result["correct"] = bool(result["correct"] and all(checks.values()))
    if len(_TraceWindow.edges) == 2:
        first, last = _TraceWindow.edges
        counters["trace_span_counters"] = {
            k: last[k] - first[k] for k in first if k.startswith("moe_")}
    return result
