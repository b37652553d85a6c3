"""The serving cell of a stack in which EVERY layer is latent attention
(``archs/pangu_ultra_moe.py``): 128 heads over one head-less row a token
a layer, a low-rank query, the shared key dims rotated, sandwich norms, a
leading dense layer and one chip's share of sigmoid-routed experts beside
a shared one; the only cache class is the latent one, paged, and whole
documents' chains of it are re-attached for every follow-up question.

It hands over to ``runners/serve.py`` as ``runners/serve_latent.py`` does
(the configuration's ``run.arch`` names the module under ``archs/`` that
is both ``model`` and ``reference``; one process runs one cell), borrows
``serve_hybrid``'s taps and its judge of the served tokens, and adds its
own checks to ``correct``.

What ``correct`` rests on, beside ``runners/serve.py``'s own checks:

- the served-token margin on a probe of 1,500 tokens (two prefill chunks
  and a remainder) and then 16 tokens decoded through the cache
  (``latent_append``, ``latent_attend`` and ``moe_decode_gmm`` on the
  chip), against the reference's ONE full pass, judged under the honest
  routing nearest to each token (``serve_hybrid.served_margin``); and the
  pooled probe, which resumes from a re-attached chain of latent blocks;
- the program's block against the reference
  (``archs/pangu_ultra_moe.block_agreement``; ``block_checks``): the
  attention mixer alone on the EXPANDED path and, through its cache, on
  the ABSORBED path against a prefix of 8,192 rotated rows at positions
  16,384-24,591; an expert layer and its held experts alone; the block's
  logits; the block THROUGH ITS CACHE;
- exact counters: nothing dropped; pairs routed = ``top_k`` x sparse
  layers x the tokens the host sent through the programs;
- the pairs the engine's expert layers COMPUTED while it served the cold
  probe against the host's recount (``HELD_PAIRS_TOLERANCE``).

Every limit below lies between the largest honest reading on the chip
and the nearest wrong one; ``benchmarks/tests/chip_pangu_variants.py``
reads the wrong ones THROUGH ``block_checks``, and PERF.md section 6
(PR 41) has the table.
"""

from __future__ import annotations

import functools
import importlib
import sys

import numpy as np

from . import common, serve
from . import serve_hybrid as hybrid

# Every reading below: one v5e chip, PERF.md section 6, PR 41 ("honest":
# the cell's own probe in eight runs of eight seeds, my chip runs 1-3,
# PR 41; the wrong programs: ``chip_pangu_variants.py``, my chip run 4,
# seed 2147486108, whose ``right`` reads what the cell reads).

# The attention mixers ALONE on the expanded path, positions 0-1,515
# (``attention_error``, the median over tokens and layers).  The program
# reads 0.00252-0.00257; the nearest wrong ones: the query's norm left
# out 0.01783, rotation off 0.0737, half-split pairs rotated 0.0902.
ATTENTION_TOLERANCE = 0.007

# The same mixers THROUGH THE CACHE on the absorbed path against a
# prefix of ``run.absorbed_prefix`` (8,192) rotated rows from position
# ``run.absorbed_start`` (16,384) on (``absorbed_error``, the median
# over 16 steps and the layers).  The limit lies between the largest the
# program gives, 0.00506-0.00507 (one step up to 0.00535), and the same
# program with the latent rows cached in 8 bits (a scale a row), the
# nearest precision below the stated bfloat16, which reads 0.00703 and
# has to read not correct: no other measure sees it (its block through
# the cache reads 0.0132, under that limit).  The query's norm left out
# reads 0.101, rotation off 0.267, the wrong pairs 0.307.
ABSORBED_TOLERANCE = 0.006

# The expert layers ALONE (router, held experts, shared expert:
# ``expert_error``).  The program reads 0.00394-0.00397.  Six tokens in
# ten choose none of the 16 held experts of 256, so at the median this
# is the shared expert and the router alone: the gates' factor left out,
# no renormalisation and int8 experts all read 0.00407 here and are held
# by the next limit; what this one alone would hold is the shared expert
# left out (0.92 in the Kimi cell, not among this cell's variants).
EXPERT_TOLERANCE = 0.012

# The held experts' partial sum ALONE (``routed_error``: the shared
# expert out of both sides, over the tokens that chose a held expert).
# The limit lies between the largest the program gives, 0.00455, and the
# program with its expert weights rounded to int8 per output channel,
# the nearest precision below the stated bfloat16, 0.01078, which has to
# read not correct.  The factor 2.5 left out reads 0.600, no
# renormalisation 6.19.
ROUTED_TOLERANCE = 0.007

# The whole block at the level of logits (``logit_error_sigma``, the
# median over positions) and THROUGH ITS CACHE at the probe's last 16
# positions (``cache_error_sigma``): five layers of bf16 rounding and a
# router whose 8th and 9th scores tie within bfloat16 in 7-9% of the
# (token, layer) pairs.  The program reads 0.0093-0.0096 and
# 0.0092-0.0094 sigma; the nearest wrong programs these alone hold: a
# post-norm left out 1.15 / 1.16; beside the other measures the query's
# norm left out 0.084 / 0.125, the factor 2.5 left out 0.090 / 0.107.
BLOCK_TOLERANCE_SIGMA = 0.03
CACHE_TOLERANCE_SIGMA = 0.03

# Pairs the engine computed on held experts while it served the cold
# probe against the host's recount with the float32 reference's router
# (``serve_hybrid.HELD_PAIRS_TOLERANCE``'s reasons: a near-tie between a
# held and an unheld expert moves the count by one).  The two differ by
# 4 of 2,744 and of 3,849 (0.15%); a share off by one expert of 16 is 6%.
HELD_PAIRS_TOLERANCE = 0.02


def block_checks(block: dict | None) -> dict:
    """The limits above on one ``block_agreement``: the part of
    ``correct`` that needs no engine, which the variants script puts
    every deliberately wrong program through as well."""
    limits = {"attention_layers": ("attention_error", ATTENTION_TOLERANCE),
              "absorbed_attention": ("absorbed_error", ABSORBED_TOLERANCE),
              "expert_layers": ("expert_error", EXPERT_TOLERANCE),
              "routed_experts": ("routed_error", ROUTED_TOLERANCE),
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


class _Spans(hybrid._Spans):
    """``serve_hybrid``'s taps, and the one place this runner meets the
    engine before ``runners/serve.py`` warms it: the reuse-prefill
    family is warmed for the chain depths this traffic reaches (the
    probe's and the documents', ``chain_blocks``) and no others.  A
    program of this stack is 11-18 MB and 10-17 s of compile, and
    ``warm()`` alone would build 40 of them for depths of 1 to 512
    blocks where 16 can occur; a depth that is missed compiles in the
    window and ``serve_compiles_in_window`` says so.  And the chunk
    lane's last-chunk program is warmed for every bucket up to the chunk
    size: ``runners/serve.py`` sends one request through the buckets the
    DOCUMENTS' lengths reach, and a first turn is a document and a
    question (``jit(fin)`` compiled in the window of this cell's first
    chip run, 12 s of its 45)."""

    chain_blocks: frozenset = frozenset()

    def tap_engine(self, engine) -> None:
        super().tap_engine(engine)
        engine.warm = functools.partial(
            engine.warm, chain_blocks=sorted(self.chain_blocks),
            chunk_finals=True)


def chain_depths(conf: dict, traffic: dict, seconds: float) -> frozenset:
    """Depths, in blocks, of the chains this traffic re-attaches: the
    pooled probe's, and every session's from its document's first
    question to its last turn."""
    gen = importlib.import_module(f"generators.{traffic['generator']}")
    block = conf["run"]["kv_block"]
    shapes = gen.shapes(traffic, seconds, block)
    lo = min(shapes["doc_lens"]) // block
    hi = shapes["max_total"] // block + 1
    return frozenset({traffic["probe_tokens"] // block, *range(lo, hi + 1)})


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
    spans, window = _Spans, hybrid._TraceWindow
    spans.probe_tokens = traffic["probe_tokens"]
    spans.chain_blocks = chain_depths(conf, traffic, float(args.seconds))
    serve._Spans, common.TraceWindow = spans, window
    result = serve.run(cell, conf, traffic, args, t_start)
    counters = result["counters"]
    checks = checks_of(arch, conf, counters, ref.block, spans.probe_edges,
                       ref.margin)
    sizes = window.engine.stats()           # levels, not differences
    print(f"[bench] mla checks {checks}: "
          f"{counters.get('moe_assignments_routed')} pairs routed for "
          f"{counters.get('moe_tokens')} tokens, "
          f"{counters.get('moe_assignments')} on held experts, "
          f"{counters.get('moe_prefill_drops')} drops; a slot holds "
          f"{sizes.get('kv_slot_bytes_latent')} bytes of latent rows and "
          f"nothing else ({sizes.get('kv_slot_bytes_state')} state, "
          f"{sizes.get('kv_slot_bytes_global')} keys and values); "
          f"{sizes.get('kv_blocks_used')} latent blocks used, "
          f"{counters.get('kv_prefix_hits')} chains re-attached, "
          f"{counters.get('kv_evictions')} blocks evicted, "
          f"{counters.get('kv_commit_skips')} commits cut short; latent "
          f"positions read {counters.get('latent_tokens_read')} for "
          f"{counters.get('latent_tokens_live')} live in "
          f"{counters.get('latent_decode_calls')} one-token calls; "
          f"{counters.get('latent_prefill_pairs')} (query, row) pairs in "
          f"the multi-token calls; tolerances: attention expanded "
          f"{ATTENTION_TOLERANCE} absorbed {ABSORBED_TOLERANCE}, expert "
          f"layers {EXPERT_TOLERANCE}, their held experts "
          f"{ROUTED_TOLERANCE}, block logits {BLOCK_TOLERANCE_SIGMA} and "
          f"through the cache {CACHE_TOLERANCE_SIGMA} sigma", flush=True)
    result["correct"] = bool(result["correct"] and all(checks.values()))
    if len(window.edges) == 2:
        first, last = window.edges
        counters["trace_span_counters"] = {
            k: last[k] - first[k] for k in first
            if k.startswith(("moe_", "latent_", "decode_kv_tokens_",
                             "kv_prefill_tokens", "prefill_chunks"))}
    return result
