"""The serving cell of a decoder-hybrid-decoder stack
(``archs/phi4flash.py``): Mamba-1 layers and window-512 differential
attention below one full layer, and above it gated memory units and
cross layers that keep no cache of their own (the memory of the last
Mamba-1 layer, the full layer's rows), whole on one chip.

It hands over to ``runners/serve.py`` as ``runners/serve_ssm.py`` does
(the configuration's ``run.arch`` names the module under ``archs/`` that
is both ``model`` and ``reference``; one process runs one cell), borrows
``serve_hybrid``'s taps (the engine's counters at the trace's edges),
and adds its own checks to ``correct``.

What ``correct`` rests on, beside ``runners/serve.py``'s own checks:

- the served-token margin on a cold probe of 1,100 tokens (more than
  two windows, four chunks of 256 and a remainder: prefill in chunks
  with the state carried and the tail left out, the last chunk under
  the last-position cut) and then 16 tokens decoded through the cache
  (the ``mamba1_step``, ``window_attend`` and ``decode_attend`` kernels
  on the chip, the cross layers over a slab they did not append to):
  all must agree with the reference's ONE full pass, every layer at
  every position (``MARGIN_TOLERANCE_SIGMA``); and the pooled probe,
  which resumes from the rings' and the states' snapshot at the
  prompt's last block edge and the full layer's pooled blocks;
- the program's stack against the reference at the level of logits, a
  Mamba-1 mixer alone, a differential attention layer alone (window,
  full and cross), a gated memory unit alone, the stack THROUGH ITS
  CACHE at the probe's last positions, and the recurrent state a
  mixer's cache holds after one chunk and some hundreds of one-token
  updates (``archs/phi4flash.block_agreement``; ``block_checks``);
- an exact counter: the layer visits the last-position cut left out of
  the window's prefills are what its plan says (``cut_counted``).

Every limit below lies between the largest honest reading on the chip
and the nearest wrong one; ``benchmarks/tests/chip_phi4flash_variants.py``
reads the wrong ones THROUGH ``block_checks``, and PERF.md section 6
(PR 45) has the table.
"""

from __future__ import annotations

import importlib
import sys

import numpy as np

from . import common, serve
from . import serve_hybrid as hybrid

# The served path's greedy tokens against the float32 reference, as
# ``serve.MARGIN_TOLERANCE_SIGMA`` defines it.  A dense stack: no router
# whose near-ties move whole experts, so the margin has no heavy tail.
# On the chip (PERF.md section 6, PR 45) the largest shortfall of 17
# served tokens reads 0.000-0.05 sigma; a wrong state, a missing chunk,
# the memory of the wrong token or rows one short serve tokens whole
# sigmas down (a random token is about 4).
MARGIN_TOLERANCE_SIGMA = 0.25

# The program's Mamba-1 mixers ALONE (projections, convolution, the
# scan from a zero state, the gate), each fed the reference's own input
# (``mixer_error``, the median over tokens and layers): one layer of
# bf16 rounding.  Readings and the nearest wrong programs: PERF.md
# section 6, PR 45.
MIXER_TOLERANCE = 0.012

# The program's differential attention layers ALONE (``window_error``
# the window layers, ``attention_error`` the full and the cross layers,
# handed the reference's keys and values): projections with their
# biases, the two softmaxes over paired heads, the subtraction under
# lambda, the pair norm, the output matrix.  ``lam = 0`` (plain
# attention) is what both have to see, a window of 511 the first: it is
# wrong only past 511 positions, in the window layers, more than half of
# the probe's.
ATTENTION_TOLERANCE = 0.012
WINDOW_TOLERANCE = 0.010

# The gated memory units ALONE (``gmu_error``), handed the reference's
# memory: two matrices and a gate.
GMU_TOLERANCE = 0.012

# The recurrent state a Mamba-1 mixer's cache holds after one chunk of
# the scan and then every later position of the probe as a one-token
# update (860 of them: on the chip the ``mamba1_step`` kernel), against
# the reference recurrence's, over each layer's slowest tenth of
# channels (``state_error``, the median).  The configuration states a
# float32 state (``run.ssm_state_dtype``); the limit lies between the
# largest reading of the program as it is and the same program with the
# state carried in bfloat16, the nearest precision below, which has to
# read not correct.
STATE_TOLERANCE = 0.007

# The whole stack at the level of logits (``logit_error_sigma``, the
# median over positions): 32 layers of bf16 rounding.
BLOCK_TOLERANCE_SIGMA = 0.08

# The stack THROUGH ITS CACHE at the probe's last 16 positions
# (``cache_error_sigma``, the median): chunked prefill with the state
# carried and the tail left out, the last-position cut, then one-token
# steps.  The memory of the previous token handed to a GMU and cross
# layers reading rows one short are what only this and the margin see.
CACHE_TOLERANCE_SIGMA = 0.12


# The cross layers ALONE as a step program runs them
# (``cross_step_error``: one row a call over the lender's slab, on the
# chip the ``decode_attend`` kernel over a slab the layer did not append
# to), at the probe's first 16 positions.  At the probe's end a cross
# layer that reads its lender's rows ONE SHORT misses a 1,100th of its
# attention, under every other number's rounding (``cache_logits`` read
# 0.0384 for 0.0383); at position 1 it misses half, and this is the
# number that sees it.
CROSS_STEP_TOLERANCE = 0.03


class _Reference:
    """What ``runners/serve.py`` sees as ``reference``: the arch
    module's plain reference, which also keeps how the program's stack
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
              "window_layers": ("window_error", WINDOW_TOLERANCE),
              "attention_layers": ("attention_error", ATTENTION_TOLERANCE),
              "memory_units": ("gmu_error", GMU_TOLERANCE),
              "carried_state": ("state_error", STATE_TOLERANCE),
              "block_logits": ("logit_error_sigma", BLOCK_TOLERANCE_SIGMA),
              "cache_logits": ("cache_error_sigma", CACHE_TOLERANCE_SIGMA),
              "cross_steps": ("cross_step_error", CROSS_STEP_TOLERANCE)}
    return {check: block is not None and bool(
        np.median(block[key]) <= limit)
        for check, (key, limit) in limits.items()}


def checks_of(arch, conf: dict, counters: dict, block: dict | None) -> dict:
    """What ``correct`` also rests on, from the window's counters and
    the probe's ``block_agreement``."""
    ran = counters.get("prefill_layer_visits", 0)
    cut = counters.get("prefill_layer_visits_cut", 0)
    n = conf["num_hidden_layers"]
    below = sum(k not in ("gmu", "cross") for k in arch.layer_kinds(conf))
    # every real token below the tail, the tail at a row a sampling
    # program's lane: the cut share is (n - below) / n less those rows
    return {"cut_counted": ran > 0 and cut > 0 and
            (ran + cut) % n == 0 and
            below * (ran + cut) // n <= ran < (ran + cut),
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
    checks = checks_of(arch, conf, counters, ref.block)
    sizes = window.engine.stats()           # levels, not differences
    answers = [f.result(60.0).tolist() for f in spans.probe_answers]
    print(f"[bench] sambay checks {checks}: the window's prefills ran "
          f"{counters.get('prefill_layer_visits')} (token, layer) visits "
          f"and left out {counters.get('prefill_layer_visits_cut')}; a "
          f"slot holds {sizes.get('kv_slot_bytes_state')} bytes in Mamba-1 "
          f"layers and {sizes.get('kv_slot_bytes_window')} in rings "
          f"whatever max_len is and {sizes.get('kv_slot_bytes_global')} in "
          f"the one full layer; {sizes.get('kv_state_snapshots')} state "
          f"snapshots held, {sizes.get('kv_state_snapshot_skips')} skipped, "
          f"{sizes.get('kv_state_reprefill_tokens')} pooled tokens "
          f"prefilled again; the cold probe's answer has "
          f"{len(set(answers[0])) if answers else 0} distinct tokens; "
          f"tolerances: mixers {MIXER_TOLERANCE}, attention "
          f"{ATTENTION_TOLERANCE}, memory units {GMU_TOLERANCE}, the "
          f"carried state {STATE_TOLERANCE}, block logits "
          f"{BLOCK_TOLERANCE_SIGMA} and through the cache "
          f"{CACHE_TOLERANCE_SIGMA} sigma, cross layers as a step runs them "
          f"{CROSS_STEP_TOLERANCE}", flush=True)
    result["correct"] = bool(result["correct"] and all(checks.values()))
    if len(window.edges) == 2:
        first, last = window.edges
        counters["trace_span_counters"] = {
            k: last[k] - first[k] for k in first
            if k.startswith(("ssm_", "decode_kv_tokens_", "borrowed_",
                             "prefill_", "decode_tokens", "lane_steps",
                             "active_lane_steps"))
            and isinstance(first[k], (int, float))}
    return result
