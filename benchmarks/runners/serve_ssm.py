"""The serving cell of a stack whose token mixer is mostly not attention
(``archs/granite_moe_hybrid.py``): nine Mamba-2 layers to one NoPE
attention layer over per-slot recurrent state beside the KV cache, and
one chip's share of the experts beside a shared MLP.

It hands over to ``runners/serve.py`` as ``runners/serve_arch.py`` and
``runners/serve_hybrid.py`` do (the configuration's ``run.arch`` names
the module under ``archs/`` that is both ``model`` and ``reference``;
one process runs one cell), borrows ``serve_hybrid``'s taps (the
engine's counters around the cold probe and at the trace's edges), and
adds its own checks to ``correct``.

What ``correct`` rests on, beside ``runners/serve.py``'s own checks:

- the served-token margin on a probe of 800 tokens (three scan chunks
  and a remainder behind its last token: prefill in chunks with state
  carried) and then 16 tokens decoded through the cache (the one-token
  recurrence, the ``ssm_step`` kernel on the chip): both must agree
  with the reference's ONE full pass (``MARGIN_TOLERANCE_SIGMA``); and
  the pooled probe, which resumes from the state snapshot at the
  prompt's last block edge;
- the program's block against the reference at the level of logits, a
  Mamba-2 mixer alone, an expert layer alone and its held experts
  alone, the block THROUGH ITS CACHE (chunked prefill with state
  carried, then one-token steps) at the probe's last positions, and
  the recurrent state a mixer's cache holds after one chunk and some
  hundreds of one-token updates against the reference recurrence's:
  what served tokens alone cannot show
  (``archs/granite_moe_hybrid.block_agreement``; ``block_checks``);
- exact counters: nothing dropped; pairs routed = ``top_k`` x layers x
  the tokens the host sent through the programs;
- the pairs the engine's expert layers COMPUTED while it served the
  cold probe against the host's recount with the reference's router
  over the same tokens (``HELD_PAIRS_TOLERANCE``).

Every limit below lies between the largest honest reading on the chip
and the nearest wrong one; ``benchmarks/tests/chip_granite_variants.py``
reads the wrong ones THROUGH ``block_checks``, and PERF.md section 6
(PR 32) has the table.  The two nearest precisions below the stated
ones each fail one limit: expert weights in int8 ``routed_experts``,
the recurrent state in bfloat16 ``carried_state``.
"""

from __future__ import annotations

import importlib
import sys

import numpy as np

from . import common, serve
from . import serve_hybrid as hybrid

# The served path's greedy tokens against the float32 reference, as
# ``serve.MARGIN_TOLERANCE_SIGMA`` defines it.  On the chip (13 runs, 11
# seeds, PERF.md section 6, PR 32): the largest shortfall of 17 served
# tokens 0.000-0.106 sigma, 15-17 of them the reference's argmax.  The
# router's 10th and 11th logit tie within bfloat16 in 17-21% of the
# (token, layer) pairs, but the 10th gate of a softmax over ten is small
# and the branch is scaled by 0.22, so the margin has no heavy tail here
# (K-EXAONE's had: ``serve_hybrid.MARGIN_TOLERANCE_SIGMA``).  A wrong
# state, a missing chunk or a broken kernel serves tokens whole sigmas
# down (a random token is about 4).
MARGIN_TOLERANCE_SIGMA = 0.25

# The program's Mamba-2 mixers ALONE (projections, convolution, the
# chunked scan from a zero state, gate and norm), each fed the
# reference's own input to it (``block_agreement``'s ``mixer_error``,
# the median over tokens and layers).  The program reads
# 0.00454-0.00485 over 11 seeds (one layer of bf16 rounding); the
# nearest wrong one, the convolution's bias left out, 0.136 (dt_bias
# left out 0.57, the norm before the gate 0.40, D left out 0.63: my chip
# run 7, PR 32).
MIXER_TOLERANCE = 0.012

# The program's attention mixer ALONE (norm, projections, scores under
# ``attention_multiplier`` with no rotation, output matrix), fed the
# reference's input to that layer (``attention_error``).  One layer in
# ten is attention and its scores are nearly flat at 1/128, so the
# whole block's logits hardly see a rotation there: this does.  The
# program reads 0.00246-0.00255; RoPE on the layer 0.0258 (its block
# logits 0.0331 beside the honest 0.0328: blind), 1 / sqrt(128) for
# 1 / 128 0.318.
ATTENTION_TOLERANCE = 0.012

# The program's expert layers ALONE (router, the held experts, the
# shared MLP), each fed the reference's own input (``expert_error``).
# The program reads 0.00398-0.00399 over 10 runs of 9 seeds (a median
# over 8160 (token, layer) pairs; my chip runs 4-7, PR 32); the nearest
# wrong program, no renormalising, 0.129 (gates normalised over the held
# experts 0.239, no shared MLP 0.973).  Expert weights in int8 read
# 0.00456 here, 1.14 times the honest reading: the shared MLP is whole
# on every token and the held experts are a fortieth of the layer's
# output, so this number is not what holds the experts' precision
# (PR 32's first pass squeezed a limit of 0.0043 between the two; the
# review asked for a number with a real gap: ``ROUTED_TOLERANCE``).
EXPERT_TOLERANCE = 0.012

# The held experts' partial sum ALONE (``routed_error``: the shared MLP
# out of both sides).  The limit lies between two readings on the chip
# (PERF.md section 6, PR 32, review pass): the largest the program
# gives, and the program with its expert weights rounded to int8 per
# output channel, the nearest precision below the stated bfloat16,
# which has to read not correct.
ROUTED_TOLERANCE = 0.007

# The recurrent state a Mamba-2 mixer's cache holds after one chunk of
# the scan and then every later position of the probe as a one-token
# update (560 of them: on the chip the ``ssm_step`` kernel), against
# the reference recurrence's, a head, over each layer's slowest tenth
# of heads (``state_error``, the median).  The configuration states a
# float32 state (``run.ssm_state_dtype``); the limit lies between the
# largest reading of the program as it is and the same program with
# the state carried in bfloat16, the nearest precision below, which
# rounds a slow head's state 560 times and has to read not correct.
STATE_TOLERANCE = 0.007

# The whole block at the level of logits (``logit_error_sigma``, the
# median over positions): ten layers of bf16 rounding.  The program
# reads 0.0330-0.0387 sigma over 11 seeds (one position up to 0.133);
# the nearest wrong program that only this sees, the residual
# multiplier left out, 0.40 (logits not divided by 16: 15.0).
BLOCK_TOLERANCE_SIGMA = 0.08

# The block THROUGH ITS CACHE at the probe's last 16 positions
# (``cache_error_sigma``: chunked prefill with the state carried, then
# the one-token recurrence, on the chip the ``ssm_step`` kernel), the
# median: the same ten layers of rounding as the whole block, plus what
# a state carried between calls adds.  A median of 16 positions, so it
# swings more than the block's: 0.0264-0.0527 over 9 seeds; the wrong
# programs read what their block reads (0.25-15).  The state carried in
# bfloat16 reads 0.0453, inside the honest range: 16 steps round a
# state 16 times; ``STATE_TOLERANCE`` is what sees that.
CACHE_TOLERANCE_SIGMA = 0.12

# Pairs the engine computed on held experts while it served the cold
# probe, against the host's recount with the float32 reference's
# router: the share by which they may differ.  In float32 they are
# equal (the CPU tests).  In bfloat16 a token whose 10th and 11th logit
# nearly tie may choose the other, and the count moves by one when
# exactly one of the two is held.  A share that is off by one expert of
# 36, or drops pairs, is 3% or more.
HELD_PAIRS_TOLERANCE = 0.015


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


def block_checks(block: dict | None) -> dict:
    """The limits above on one ``block_agreement``: the part of
    ``correct`` that needs no engine, which the variants script puts
    every deliberately wrong program through as well."""
    limits = {"mixer_layers": ("mixer_error", MIXER_TOLERANCE),
              "attention_layers": ("attention_error", ATTENTION_TOLERANCE),
              "expert_layers": ("expert_error", EXPERT_TOLERANCE),
              "routed_experts": ("routed_error", ROUTED_TOLERANCE),
              "carried_state": ("state_error", STATE_TOLERANCE),
              "block_logits": ("logit_error_sigma", BLOCK_TOLERANCE_SIGMA),
              "cache_logits": ("cache_error_sigma", CACHE_TOLERANCE_SIGMA)}
    return {check: block is not None and bool(
        np.median(block[key]) <= limit)
        for check, (key, limit) in limits.items()}


def checks_of(arch, conf: dict, counters: dict, block: dict | None,
              probe_edges: list[dict]) -> dict:
    """What ``correct`` also rests on, from the window's counters, the
    probe's ``block_agreement`` and the counters around the cold
    probe."""
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
        "nothing_dropped": counters.get("moe_prefill_drops", -1) == 0,
        "every_token_routed": routed > 0 and
        counters.get("moe_assignments_routed", -1) == routed,
        "held_pairs_recount": bool(
            computed and recount and abs(computed - recount)
            <= HELD_PAIRS_TOLERANCE * recount),
        **block_checks(block)}


def run(cell: dict, conf: dict, traffic: dict, args, t_start: float) -> dict:
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    ref = _Reference(arch)
    sys.modules["model"], sys.modules["reference"] = arch, ref
    serve.MARGIN_TOLERANCE_SIGMA = MARGIN_TOLERANCE_SIGMA
    # one request of PROBE_NEW tokens feeds PROBE_NEW - 1 back: with 17
    # and 4 token steps a sync the engine's programs process exactly
    # the tokens the reference is given (serve_hybrid.PROBE_NEW)
    serve.PROBE_NEW = hybrid.PROBE_NEW
    spans, window = hybrid._Spans, hybrid._TraceWindow
    spans.probe_tokens = traffic["probe_tokens"]
    serve._Spans, common.TraceWindow = spans, window
    result = serve.run(cell, conf, traffic, args, t_start)
    counters = result["counters"]
    checks = checks_of(arch, conf, counters, ref.block, spans.probe_edges)
    sizes = window.engine.stats()           # levels, not differences
    answers = [f.result(60.0).tolist() for f in spans.probe_answers]
    print(f"[bench] ssm checks {checks}: "
          f"{counters.get('moe_assignments_routed')} pairs routed for "
          f"{counters.get('moe_tokens')} tokens, "
          f"{counters.get('moe_assignments')} on held experts, "
          f"{counters.get('moe_prefill_drops')} drops; a slot holds "
          f"{sizes.get('kv_slot_bytes_state')} bytes in state-space layers "
          f"whatever max_len is and {sizes.get('kv_slot_bytes_global')} in "
          f"the attention layer; {sizes.get('kv_state_snapshots')} state "
          f"snapshots held, {sizes.get('kv_state_snapshot_skips')} skipped, "
          f"{sizes.get('kv_state_reprefill_tokens')} pooled tokens "
          f"prefilled again; the cold probe's answer has "
          f"{len(set(answers[0])) if answers else 0} distinct tokens; "
          f"tolerances: mixers {MIXER_TOLERANCE}, attention "
          f"{ATTENTION_TOLERANCE}, expert layers {EXPERT_TOLERANCE}, their "
          f"held experts {ROUTED_TOLERANCE}, the carried state "
          f"{STATE_TOLERANCE}, block logits {BLOCK_TOLERANCE_SIGMA} and "
          f"through the cache {CACHE_TOLERANCE_SIGMA} sigma",
          flush=True)
    result["correct"] = bool(result["correct"] and all(checks.values()))
    if len(window.edges) == 2:
        first, last = window.edges
        counters["trace_span_counters"] = {
            k: last[k] - first[k] for k in first
            if k.startswith(("moe_", "ssm_", "decode_kv_tokens_"))}
    return result
