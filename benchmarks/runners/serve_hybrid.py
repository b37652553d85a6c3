"""The serving cell of a stack whose layers differ and that holds a
share of its experts (``archs/exaone_moe.py``): window and global
attention layers over a two-class KV cache, a leading dense layer,
sigmoid-routed experts of which this device holds some, a shared one.

It hands over to ``runners/serve.py`` as ``runners/serve_arch.py`` does
(the configuration's ``run.arch`` names the module under ``archs/`` that
is both ``model`` and ``reference``; one process runs one cell), and
adds its own checks to ``correct``.  ``serve_arch.expert_checks`` counts
``top_k x num_hidden_layers x tokens`` pairs, which is wrong twice
here: layer 0 is dense, and of the pairs a router routes this device
computes those that land on the experts it holds.

What ``correct`` rests on, beside ``runners/serve.py``'s own checks:

- the served-token margin on a probe long enough that four windows lie
  behind its last token (``MARGIN_TOLERANCE_SIGMA``), judged under the
  honest routing nearest to each token (``served_margin``: why, there);
- the program's block against the reference at the level of logits, its
  sparse layers alone, and the held experts' partial sum alone
  (``archs/exaone_moe.block_agreement``);
- exact counters: nothing dropped; pairs routed = ``top_k`` x sparse
  layers x the tokens the host sent through the programs;
- the pairs the engine's expert layers COMPUTED while it served the
  cold probe against the host's recount with the reference's router
  over the same tokens (``HELD_PAIRS_TOLERANCE``).

In a traced run it reads the engine's counters at the trace's own
edges, so that the expert and window readers count what the span held.
"""

from __future__ import annotations

import importlib
import sys

import numpy as np

from . import common, serve

# The served path's greedy tokens against the float32 reference, as
# ``serve.MARGIN_TOLERANCE_SIGMA`` defines it, but judged by
# ``served_margin`` below and not by ``runners/serve.py``, whose own
# comparison takes the float32 pass's routing for the only honest one.
# Here it is not.  The router's 8th and 9th selection scores lie 0.001
# apart in the median, bfloat16 activations move them by as much, and
# the program's block chooses another set than the reference in 10-13%
# of the (token, sparse layer) pairs, as any bfloat16 program must.
# Where one of the two experts is held here and the other is not
# (2.9% of the pairs, 14% of the positions in some layer), a WHOLE
# expert's output, 12-19% of the residual stream's norm, enters or
# leaves this device's partial sum, and that position's logits move by
# 0.10-0.35 sigma where a position without one moves by 0.017 (PERF.md
# section 6, PR 30, second pass).  The largest shortfall of 17 served
# tokens against the one float32 routing is therefore heavy-tailed in
# an honest program: 0.000-0.127 sigma in eleven runs of the cell,
# 0.2856 in a twelfth, 0.6209 on the driver's seed 1642149975 (one
# token, whose selection scores tie within 0.0008 in one layer), beside
# the 0.75 of a program without the gates' factor: no limit on THAT
# number parts the honest from the wrong.  So the limit stays and the
# comparison learns what a near-tie is: a token over the limit is
# judged again under the reference with that token's own near-ties
# resolved the other way (``archs/exaone_moe.tie_aware_shortfall``),
# and is honest if ONE such routing puts it within the limit.  Wrong
# programs stay wrong: full attention on the window layers reads 1.85,
# no shared expert 3.99 (a random token is about 4) under the plain
# reference, and what the search makes of a wrong program's tokens is
# in PERF.md beside the honest readings.  RoPE on the global layers
# reads an honest 0.1445: this margin never saw it, the block's logits
# below do.
MARGIN_TOLERANCE_SIGMA = 0.35

# Two selection scores (``s + b``) this close are a near-tie: the
# search may swap the two.  Between the two readings: the program's
# block (bfloat16) chose across gaps of up to 0.0116 where it first
# left the reference's held set (93 positions of 672, median 0.0012;
# PERF.md section 6), and 0.05 is the spread of the selection bias
# itself, past which a swap is a different router and not a rounding.
TIE_DELTA = 0.02

# The program's sparse layers ALONE (router, the held experts, the
# shared expert), each fed the reference's own input to it
# (block_agreement's ``expert_error``, the median over tokens and
# layers): 0.00484-0.00487 over 9 seeds; the nearest wrong program, a
# softmax router, 0.12; no factor 0.18, no shared expert 0.96, gates
# normalised over the held experts 1.23.
EXPERT_TOLERANCE = 0.015

# The held experts' partial sum alone (``held_expert_error``: the
# shared expert out of both sides, tokens with a held expert).  The
# limit lies between the largest reading of the program and what the
# REFERENCE's own held experts give in float32 arithmetic with their
# weights rounded to int8 per output channel, the nearest precision
# below the stated bfloat16, which is not correct: PERF.md section 6,
# PR 30 has both readings.
HELD_EXPERT_TOLERANCE = 0.0075

# The whole block at the level of logits (``logit_error_sigma``, the
# median over positions): eight layers of bf16 rounding, window and
# global attention and the dense layer included: 0.0148-0.0169 sigma
# over 9 seeds.  RoPE on the global layers 0.103, the gates' factor
# left out 0.22, a softmax router 0.25, full attention on the window
# layers 0.45.
BLOCK_TOLERANCE_SIGMA = 0.04

# Pairs the engine computed on held experts while it served the cold
# probe, against the host's recount with the float32 reference's
# router: the share by which they may differ.  In float32 they are
# equal (the CPU tests).  In bfloat16 a token whose 8th and 9th score
# nearly tie may choose the other, and the count moves by one when
# exactly one of the two is held: a few tens of 4,600, either way.  A
# share that is off by one expert of 16, or drops pairs, is 6% or more.
HELD_PAIRS_TOLERANCE = 0.02

# one request of PROBE_NEW tokens feeds PROBE_NEW - 1 back: with 17 and
# 4 token steps a sync the engine's programs process exactly the tokens
# the reference is given (prompt + all but the last of the answer)
PROBE_NEW = 17


class _Reference:
    """What ``runners/serve.py`` sees as ``reference``: the arch
    module's plain reference, which also keeps how the program's block
    compared on the same probe (``block``)."""

    def __init__(self, arch):
        self.arch, self.block, self.margin = arch, None, None

    def logits(self, conf, params, ids):
        ref = self.arch.reference(conf, params, ids)
        self.block = self.arch.block_agreement(conf, params, ids, ref)
        self.margin = served_margin(
            self.arch, conf, params, ids, ref,
            [[int(t) for t in f.result(60.0)] for f in _Spans.probe_answers])
        return ref["logits"]


def served_margin(arch, conf: dict, params, ids, ref: dict,
                  answers: list) -> dict | None:
    """``runners/serve.py``'s two comparisons of the probe's tokens
    with the reference, each under the honest routing nearest to the
    token (``MARGIN_TOLERANCE_SIGMA``'s comment): ``cold``, the largest
    shortfall of the cold probe's tokens, and ``pooled``, the shortfall
    of the pooled probe's token where it first differs from the cold
    one's (0 where it never does).  ``ids`` is the probe and all but
    the last of the cold answer, as ``serve.py`` hands it over."""
    if len(answers) != 2 or any(len(a) != PROBE_NEW for a in answers):
        return None                 # serve.py's probe_tokens says so
    cold, pooled = answers
    first = ids.shape[1] - (PROBE_NEW - 1) - 1   # predicts cold[0]

    def judge(j, token):
        found = arch.tie_aware_shortfall(
            conf, params, ids, ref, first + j, token,
            limit=MARGIN_TOLERANCE_SIGMA, delta=TIE_DELTA)
        if found["passes"]:
            swaps = "; ".join(
                f"layer {i}: expert {out} out, {into} in, scores {gap:.5f} "
                f"apart" for i, out, into, gap in found["swaps"]) or "none"
            print(f"[bench] answer token {j} ({token}) is {found['plain']:.4f}"
                  f" sigma under the reference's best; under the nearest "
                  f"honest routing ({swaps}) {found['shortfall']:.4f}, after "
                  f"{found['passes']} more reference passes", flush=True)
        return found["shortfall"]

    cold_short = [judge(j, t) for j, t in enumerate(cold)]
    split = next((j for j, (a, b) in enumerate(zip(cold, pooled)) if a != b),
                 None)
    pooled_short = 0.0 if split is None else judge(split, pooled[split])
    print(f"[bench] served margin under the nearest honest routing (ties "
          f"within {TIE_DELTA}): cold largest {max(cold_short):.4f}, pooled "
          f"{pooled_short:.4f} at its first difference ({split}) (tolerance "
          f"{MARGIN_TOLERANCE_SIGMA} sigma)", flush=True)
    return {"cold": max(cold_short), "pooled": pooled_short}


class _Spans(serve._Spans):
    """``runners/serve.py`` hands its engine to ``tap_engine`` once: the
    one place this runner can meet it.  The first two requests of the
    probe's shape are the cold probe and the pooled one: the engine's
    counters are read as each is submitted, so their difference is the
    cold probe's own (nothing else is in flight then, and by the
    second submit the first one's last tick has been counted), and
    their futures are kept: ``served_margin`` needs the last token of
    each answer, which the reference is never given."""

    probe_tokens = None
    probe_edges: list[dict] = []
    probe_answers: list = []

    def tap_engine(self, engine) -> None:
        super().tap_engine(engine)
        _TraceWindow.engine = engine
        tapped, edges = engine.submit, self.probe_edges

        def submit(prompt, max_new_tokens, *a, **kw):
            probe = (len(edges) < 2 and max_new_tokens == PROBE_NEW
                     and len(prompt) == self.probe_tokens)
            if probe:
                edges.append(engine.stats())
            answer = tapped(prompt, max_new_tokens, *a, **kw)
            if probe:
                self.probe_answers.append(answer)
            return answer

        engine.submit = submit


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


def checks_of(arch, conf: dict, counters: dict, block: dict | None,
              probe_edges: list[dict], margin: dict | None) -> dict:
    """What ``correct`` also rests on, from the window's counters, the
    probe's ``block_agreement`` and ``served_margin`` and the counters
    around the cold probe."""
    routed = (conf["num_experts_per_tok"] * arch.sparse_layers(conf)
              * counters.get("moe_tokens", 0))
    computed = (probe_edges[1]["moe_assignments"]
                - probe_edges[0]["moe_assignments"]
                if len(probe_edges) == 2 else None)
    recount = block["held_pairs"] if block else None
    print(f"[bench] held pairs on the cold probe: the engine computed "
          f"{computed}, the host recounts {recount} (tolerance "
          f"{HELD_PAIRS_TOLERANCE})", flush=True)
    return {
        "served_margin": margin is not None and
        margin["cold"] <= MARGIN_TOLERANCE_SIGMA,
        "pooled_margin": margin is not None and
        margin["pooled"] <= MARGIN_TOLERANCE_SIGMA,
        "nothing_dropped": counters.get("moe_prefill_drops", -1) == 0,
        "every_token_routed": routed > 0 and
        counters.get("moe_assignments_routed", -1) == routed,
        "held_pairs_recount": bool(
            computed and recount and abs(computed - recount)
            <= HELD_PAIRS_TOLERANCE * recount),
        "expert_layers": block is not None and bool(
            np.median(block["expert_error"]) <= EXPERT_TOLERANCE),
        "held_experts": block is not None and bool(
            np.median(block["held_expert_error"]) <= HELD_EXPERT_TOLERANCE),
        "block_logits": block is not None and bool(
            np.median(block["logit_error_sigma"]) <= BLOCK_TOLERANCE_SIGMA),
    }


def run(cell: dict, conf: dict, traffic: dict, args, t_start: float) -> dict:
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    ref = _Reference(arch)
    sys.modules["model"], sys.modules["reference"] = arch, ref
    # serve.py's own comparison of the probe's tokens knows one routing
    # (MARGIN_TOLERANCE_SIGMA's comment): served_margin makes both of
    # its comparisons at the same limit, and serve.py keeps the rest
    # (both answers whole, the pooled one a prefix hit)
    serve.MARGIN_TOLERANCE_SIGMA = float("inf")
    serve.PROBE_NEW = PROBE_NEW
    _Spans.probe_tokens = traffic["probe_tokens"]
    serve._Spans, common.TraceWindow = _Spans, _TraceWindow
    result = serve.run(cell, conf, traffic, args, t_start)
    counters = result["counters"]
    checks = checks_of(arch, conf, counters, ref.block, _Spans.probe_edges,
                       ref.margin)
    sizes = _TraceWindow.engine.stats()     # levels, not differences
    print(f"[bench] hybrid checks {checks}: "
          f"{counters.get('moe_assignments_routed')} pairs routed for "
          f"{counters.get('moe_tokens')} tokens, "
          f"{counters.get('moe_assignments')} on held experts, "
          f"{counters.get('moe_prefill_drops')} drops; a slot holds "
          f"{sizes.get('kv_slot_bytes_window')} bytes in window layers "
          f"and {sizes.get('kv_slot_bytes_global')} in global layers; "
          f"tolerances: expert layers {EXPERT_TOLERANCE}, held experts "
          f"{HELD_EXPERT_TOLERANCE}, block logits {BLOCK_TOLERANCE_SIGMA} "
          f"sigma", flush=True)
    result["correct"] = bool(result["correct"] and all(checks.values()))
    if len(_TraceWindow.edges) == 2:
        first, last = _TraceWindow.edges
        counters["trace_span_counters"] = {
            k: last[k] - first[k] for k in first
            if k.startswith(("moe_", "decode_kv_tokens_"))}
    return result
