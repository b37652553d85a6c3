"""The serving cell of a stack of delta-rule linear-attention layers and
latent attention layers (``archs/kimi_linear.py``): three KDA layers to
one NoPE latent-attention layer, a head-less latent cache paged beside
per-slot recurrent state, a leading dense layer and one chip's share of
sigmoid-routed experts beside a shared one.

It hands over to ``runners/serve.py`` as ``runners/serve_ssm.py`` does
(the configuration's ``run.arch`` names the module under ``archs/`` that
is both ``model`` and ``reference``; one process runs one cell), borrows
``serve_hybrid``'s taps and its judge of the served tokens, and adds its
own checks to ``correct``.

What ``correct`` rests on, beside ``runners/serve.py``'s own checks:

- the served-token margin on a probe of 1,100 tokens (four prefill
  chunks and a remainder; KDA chunks with a remainder) and then 16
  tokens decoded through the cache (``kda_step``, ``latent_append`` and
  ``latent_attend`` on the chip), against the reference's ONE full
  pass, judged under the honest routing nearest to each token
  (``serve_hybrid.served_margin``: the router is K-EXAONE's kind, a
  sigmoid with a selection bias over a held share, and has its heavy
  tail); and the pooled probe, which resumes from latent blocks and the
  state snapshot at the prompt's last block edge;
- the program's block against the reference
  (``archs/kimi_linear.block_agreement``; ``block_checks``): a KDA mixer
  alone; a latent attention mixer alone on the EXPANDED path and,
  through its cache, on the ABSORBED path against a latent prefix of
  8,192 positions; an expert layer and its held experts alone; the
  block's logits; the block THROUGH ITS CACHE; the KDA state after one
  chunk and some hundreds of one-token updates;
- exact counters: nothing dropped; pairs routed = ``top_k`` x sparse
  layers x the tokens the host sent through the programs;
- the pairs the engine's expert layers COMPUTED while it served the
  cold probe against the host's recount (``HELD_PAIRS_TOLERANCE``).

Every limit below lies between the largest honest reading on the chip
and the nearest wrong one; ``benchmarks/tests/chip_kimi_variants.py``
reads the wrong ones THROUGH ``block_checks``, and PERF.md section 6
(PR 37) has the table.
"""

from __future__ import annotations

import importlib
import sys

import numpy as np

from . import common, serve
from . import serve_hybrid as hybrid

# Every reading below: one v5e chip, PERF.md section 6, PR 37 ("honest":
# the cell's own probe in ten runs of nine seeds, my chip runs 1 and 3;
# the wrong programs: ``chip_kimi_variants.py``, seed 2147485301).

# The KDA mixers ALONE (projections, convolutions, the chunked delta
# rule from a zero state, gate and norm), each fed the reference's own
# input (``mixer_error``, the median over tokens and layers).  The
# program reads 0.00401-0.00404; the nearest wrong ones: the delta
# correction left out 0.453, the decay taken a head and not a channel
# 0.528.
MIXER_TOLERANCE = 0.015

# The latent attention mixers ALONE on the expanded path
# (``attention_error``).  The program reads 0.00260-0.00265; ``k_pe``
# dropped from the scores 0.0939, rotation applied 0.1107 (the whole
# block's logits read 0.084 and 0.088 there, beside an honest 0.029-0.040
# and the limit 0.06: seen, by less).
ATTENTION_TOLERANCE = 0.012

# The same mixers THROUGH THE CACHE on the absorbed path against a
# prefix of ``run.absorbed_prefix`` (8,192) positions
# (``absorbed_error``, the median over 16 steps and the layers).  The
# limit lies between the largest the program gives, 0.00434-0.00441
# (one step up to 0.00463), and the same program with the latent rows
# cached in 8 bits (a scale a row), the nearest precision below the
# stated bfloat16, which reads 0.00625 and has to read not correct: no
# other measure sees it (its block through the cache reads 0.0227,
# inside the honest range).  ``k_pe`` dropped reads 0.336, rotation
# 0.391.
ABSORBED_TOLERANCE = 0.0053

# The expert layers ALONE (router, held experts, shared expert:
# ``expert_error``).  The program reads 0.00405-0.00407; the selection
# bias left out 0.295, no shared expert 0.921.  Expert weights in int8
# read 0.00552 here, 1.36 times the honest reading: the shared expert is
# whole on every token, so this number is not what holds the experts'
# precision (``serve_ssm.EXPERT_TOLERANCE``'s comment): the next is.
EXPERT_TOLERANCE = 0.012

# The held experts' partial sum ALONE (``routed_error``: the shared
# expert out of both sides).  The limit lies between the largest the
# program gives, 0.00452-0.00454, and the program with its expert
# weights rounded to int8 per output channel, the nearest precision
# below the stated bfloat16, 0.01051, which has to read not correct.
ROUTED_TOLERANCE = 0.007

# The recurrent state a KDA mixer's cache holds after one chunk and then
# every later position of the probe as a one-token update (860 of them:
# on the chip ``kda_step``), against the reference recurrence's, a head,
# over each layer's slowest tenth of heads (``state_error``, the
# median).  The configuration states a float32 state
# (``run.kda_state_dtype``); the limit lies between the largest reading
# of the program as it is, 0.00367-0.00383 (one head up to 0.00426), and
# the same program with the state carried in bfloat16, the nearest
# precision below, 0.01262, which has to read not correct.
STATE_TOLERANCE = 0.006

# The whole block at the level of logits (``logit_error_sigma``, the
# median over positions) and THROUGH ITS CACHE at the probe's last 16
# positions (``cache_error_sigma``): eight layers of bf16 rounding and a
# router whose 8th and 9th selection scores tie within bfloat16 in
# 18-21% of the (token, layer) pairs.  The program reads 0.0286-0.0395
# and 0.0198-0.0455 sigma (a median of 16 positions swings more); the
# wrong programs that these alone hold read 0.36 / 0.33 (no selection
# bias) and above.
BLOCK_TOLERANCE_SIGMA = 0.06
CACHE_TOLERANCE_SIGMA = 0.10

# Pairs the engine computed on held experts while it served the cold
# probe against the host's recount with the float32 reference's router
# (``serve_hybrid.HELD_PAIRS_TOLERANCE``'s reasons: a near-tie between a
# held and an unheld expert moves the count by one).  The two differ by
# 18-44 of about 15,000 (0.3%); a share off by one expert of 64 is 1.6%.
HELD_PAIRS_TOLERANCE = 0.012


def block_checks(block: dict | None) -> dict:
    """The limits above on one ``block_agreement``: the part of
    ``correct`` that needs no engine, which the variants script puts
    every deliberately wrong program through as well."""
    limits = {"mixer_layers": ("mixer_error", MIXER_TOLERANCE),
              "attention_layers": ("attention_error", ATTENTION_TOLERANCE),
              "absorbed_attention": ("absorbed_error", ABSORBED_TOLERANCE),
              "expert_layers": ("expert_error", EXPERT_TOLERANCE),
              "routed_experts": ("routed_error", ROUTED_TOLERANCE),
              "carried_state": ("state_error", STATE_TOLERANCE),
              "block_logits": ("logit_error_sigma", BLOCK_TOLERANCE_SIGMA),
              "cache_logits": ("cache_error_sigma", CACHE_TOLERANCE_SIGMA)}
    return {check: block is not None and bool(
        np.median(block[key]) <= limit)
        for check, (key, limit) in limits.items()}


def checks_of(arch, conf: dict, counters: dict, block: dict | None,
              probe_edges: list[dict], margin: dict | None) -> dict:
    """What ``correct`` also rests on, from the window's counters, the
    probe's ``block_agreement`` and ``served_margin`` and the counters
    around the cold probe."""
    routed = (conf["num_experts_per_token"] * arch.sparse_layers(conf)
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
        margin["cold"] <= hybrid.MARGIN_TOLERANCE_SIGMA,
        "pooled_margin": margin is not None and
        margin["pooled"] <= hybrid.MARGIN_TOLERANCE_SIGMA,
        "nothing_dropped": counters.get("moe_prefill_drops", -1) == 0,
        "every_token_routed": routed > 0 and
        counters.get("moe_assignments_routed", -1) == routed,
        "held_pairs_recount": bool(
            computed and recount and abs(computed - recount)
            <= HELD_PAIRS_TOLERANCE * recount),
        **block_checks(block)}


def run(cell: dict, conf: dict, traffic: dict, args, t_start: float) -> dict:
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    # serve_hybrid's: the plain reference, which also keeps the block's
    # agreement and the served tokens' margin on the same probe
    ref = hybrid._Reference(arch)
    sys.modules["model"], sys.modules["reference"] = arch, ref
    # serve.py's own comparison of the probe's tokens knows one routing:
    # served_margin makes both comparisons at serve_hybrid's limit, and
    # serve.py keeps the rest (both answers whole, the pooled one a hit)
    serve.MARGIN_TOLERANCE_SIGMA = float("inf")
    serve.PROBE_NEW = hybrid.PROBE_NEW
    spans, window = hybrid._Spans, hybrid._TraceWindow
    spans.probe_tokens = traffic["probe_tokens"]
    serve._Spans, common.TraceWindow = spans, window
    result = serve.run(cell, conf, traffic, args, t_start)
    counters = result["counters"]
    checks = checks_of(arch, conf, counters, ref.block, spans.probe_edges,
                       ref.margin)
    sizes = window.engine.stats()           # levels, not differences
    print(f"[bench] latent checks {checks}: "
          f"{counters.get('moe_assignments_routed')} pairs routed for "
          f"{counters.get('moe_tokens')} tokens, "
          f"{counters.get('moe_assignments')} on held experts, "
          f"{counters.get('moe_prefill_drops')} drops; a slot holds "
          f"{sizes.get('kv_slot_bytes_latent')} bytes of latent rows and "
          f"{sizes.get('kv_slot_bytes_state')} of delta-rule state whatever "
          f"max_len is; {sizes.get('kv_state_snapshots')} state snapshots "
          f"held, {sizes.get('kv_state_snapshot_skips')} skipped, "
          f"{sizes.get('kv_blocks_used')} latent blocks used; latent "
          f"positions read {counters.get('latent_tokens_read')} for "
          f"{counters.get('latent_tokens_live')} live; tolerances: KDA "
          f"mixers {MIXER_TOLERANCE}, latent attention expanded "
          f"{ATTENTION_TOLERANCE} absorbed {ABSORBED_TOLERANCE}, expert "
          f"layers {EXPERT_TOLERANCE}, their held experts "
          f"{ROUTED_TOLERANCE}, the carried state {STATE_TOLERANCE}, block "
          f"logits {BLOCK_TOLERANCE_SIGMA} and through the cache "
          f"{CACHE_TOLERANCE_SIGMA} sigma", flush=True)
    result["correct"] = bool(result["correct"] and all(checks.values()))
    if len(window.edges) == 2:
        first, last = window.edges
        counters["trace_span_counters"] = {
            k: last[k] - first[k] for k in first
            if k.startswith(("moe_", "ssm_", "latent_", "decode_kv_tokens_"))}
    return result
