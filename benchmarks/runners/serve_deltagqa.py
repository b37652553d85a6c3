"""The serving cell of a stack of delta-rule linear-attention layers and
gated GQA layers without positions (``archs/solar_open2.py``): three KDA
layers to one full layer of 64 query heads on 8 KV heads, head rows
paged by blocks beside per-slot recurrent state paged by snapshots, one
chip's share of sigmoid-routed experts beside a shared one, under agent
sessions whose contexts are 32k-114k rows.

It hands over to ``runners/serve.py`` as ``runners/serve_latent.py``
does (the configuration's ``run.arch`` names the module under ``archs/``
that is both ``model`` and ``reference``; one process runs one cell),
borrows ``serve_hybrid``'s taps and its judge of the served tokens, and
adds its own checks to ``correct``.

What ``correct`` rests on, beside ``runners/serve.py``'s own checks:

- the served-token margin on a probe of 1,500 tokens (chunks and a
  remainder) and then 16 tokens decoded through the cache (``kda_step``,
  ``decode_append`` and ``decode_attend`` on the chip) against the
  reference's ONE full pass, judged under the honest routing nearest to
  each token (``serve_hybrid.served_margin``); the pooled probe; and a
  TWO-TURN SESSION: the probe's history and its answer come back with
  96 new tokens, the engine re-attaches the GQA layer's blocks and the
  state snapshot at the probe's last prompt block edge and re-prefills
  the tail, and its 17 tokens are judged against the reference's one
  pass over the whole history (``session_margin``);
- the program's block against the reference
  (``archs/solar_open2.block_agreement``; ``block_checks``): the KDA
  mixers alone; the gated GQA mixers alone; the expert layers and the
  held experts' partial sum alone; the KDA state after one chunk and
  about a thousand one-token updates; the block's logits; the block
  THROUGH ITS CACHE;
- the gated GQA mixers THROUGH THE CACHE against a prefix of 65,536 rows
  (``long_prefix_agreement``, run before the engine takes the memory):
  the last chunk on the multi-token path that reads the live prefix in
  tiles, and 16 one-token steps after it (``decode_attend``);
- exact counters: nothing dropped; pairs routed = ``top_k`` x sparse
  layers x the tokens the host sent through the programs; the pairs the
  engine COMPUTED on held experts while it served the cold probe
  against the host's recount (``HELD_PAIRS_TOLERANCE``).

Every limit below lies between the largest honest reading on the chip
and the nearest wrong one; ``benchmarks/tests/chip_solar_variants.py``
reads the wrong ones THROUGH ``block_checks``, and PERF.md section 6
(PR 52) has the table.
"""

from __future__ import annotations

import functools
import importlib
import sys

import numpy as np

from . import common, serve
from . import serve_hybrid as hybrid

# Every reading below: one v5e chip, PERF.md section 6, PR 52 ("honest":
# the cell's own runs, seeds 2147485201 / 2147485202, and the variants
# script's right program, seed 2147485203; the wrong programs:
# ``chip_solar_variants.py``, seed 2147485203, my chip run 11).

# The KDA mixers ALONE with beta in [0, 2] (``mixer_error``, the median
# over tokens and layers).  The program reads 0.00447-0.00448; beta left
# undoubled 0.255.
MIXER_TOLERANCE = 0.015

# The gated GQA mixers ALONE in a full forward (``attention_error``).
# The program reads 0.00615-0.00617; the gate left out 0.999, a rotation
# applied 0.983.
ATTENTION_TOLERANCE = 0.012

# The same mixers THROUGH THE CACHE against a prefix of
# ``run.long_prefix`` (65,536) rows: the last 64 positions of the last
# chunk (``long_chunk_error``: the tiled multi-token path) and 16
# one-token steps after it (``long_step_error``: ``decode_attend``), the
# median over positions.  Each limit lies between the largest the
# program gives (chunk 0.00370, one position up to 0.00388; step
# 0.00406-0.00407, one up to 0.00422) and the same program with its K /
# V rows cached in 8 bits (a scale a row), the nearest precision below
# the stated bfloat16: 0.00522 and 0.00553, which has to read not
# correct.  The loop stopping one tile short reads 0.01515 on the chunk
# (one position 0.019) and nothing anywhere else; the gate left out or a
# rotation applied 1.0 on both.
LONG_CHUNK_TOLERANCE = 0.0045
LONG_STEP_TOLERANCE = 0.0048

# The expert layers ALONE (``expert_error``: the program 0.00392-0.00393,
# the selection bias left out 0.111) and the held experts' partial sum
# ALONE (``routed_error``: the shared expert out of both sides; the
# program 0.00439-0.00446, expert weights in int8 per output channel
# 0.01029, which has to read not correct and which ``expert_error``,
# 0.00408 there, does not see).
EXPERT_TOLERANCE = 0.012
ROUTED_TOLERANCE = 0.007

# The recurrent state a KDA mixer's cache holds after one chunk of 1,024
# and then every later position of the probe as a one-token update (492
# of them: on the chip ``kda_step``), a slow head (``state_error``, the
# median): the float32 state reads 0.00431-0.00433 (one head up to
# 0.00467), the bfloat16 state 0.01078, beta undoubled 0.512.
STATE_TOLERANCE = 0.007

# The whole block at the level of logits (0.00945-0.00959 sigma) and
# THROUGH ITS CACHE (0.01047-0.01049; with 8-bit rows 0.01508, int8
# experts 0.01271: seen, by the limits above).  The wrong programs these
# alone would hold: a rotation 0.077 / 0.055, the gate left out 0.079 /
# 0.058, no selection bias 0.124 / 0.118, beta undoubled 0.204 / 0.204.
BLOCK_TOLERANCE_SIGMA = 0.04
CACHE_TOLERANCE_SIGMA = 0.04

# Pairs the engine computed on held experts while it served the cold
# probe against the host's recount with the float32 reference's router
# (``serve_latent.HELD_PAIRS_TOLERANCE``'s reasons): 7,107 for 7,114
# (0.1%); a share off by one expert of 40 is 2.5%.
HELD_PAIRS_TOLERANCE = 0.012

SESSION_NEW = 96            # tokens the session probe's second turn adds


def block_checks(block: dict | None, long: dict | None = None) -> dict:
    """The limits above on one ``block_agreement`` and one
    ``long_prefix_agreement``: the part of ``correct`` that needs no
    engine, which the variants script puts every deliberately wrong
    program through as well."""
    limits = {"mixer_layers": ("mixer_error", MIXER_TOLERANCE),
              "attention_layers": ("attention_error", ATTENTION_TOLERANCE),
              "expert_layers": ("expert_error", EXPERT_TOLERANCE),
              "routed_experts": ("routed_error", ROUTED_TOLERANCE),
              "carried_state": ("state_error", STATE_TOLERANCE),
              "block_logits": ("logit_error_sigma", BLOCK_TOLERANCE_SIGMA),
              "cache_logits": ("cache_error_sigma", CACHE_TOLERANCE_SIGMA)}
    far = {"long_chunk": ("long_chunk_error", LONG_CHUNK_TOLERANCE),
           "long_step": ("long_step_error", LONG_STEP_TOLERANCE)}
    out = {check: block is not None and bool(
        np.median(block[key]) <= limit)
        for check, (key, limit) in limits.items()}
    out.update({check: long is not None and bool(
        np.median(long[key]) <= limit)
        for check, (key, limit) in far.items()})
    return out


class _Model:
    """What ``runners/serve.py`` sees as ``model``: the arch module,
    which also compares the gated GQA mixers through the cache against
    the long prefix WHILE THE MEMORY IS FREE: between the weights and
    the engine, whose slots and pool leave no room for 0.9 GB of float32
    keys, values and scores."""

    def __init__(self, arch):
        self.arch, self.conf, self.long = arch, None, None

    def transformer_config(self, conf, **kw):
        self.conf = conf
        return self.arch.transformer_config(conf, **kw)

    def init_params(self, cfg, seed, param_dtype, split_layers=True):
        params = self.arch.init_params(cfg, seed, param_dtype, split_layers)
        self.long = self.arch.long_prefix_agreement(self.conf, params, seed)
        return params


class _Reference(hybrid._Reference):
    """``serve_hybrid``'s reference, and the two-turn session probe: the
    one place this runner is called between the probes and the client."""

    session = None

    def logits(self, conf, params, ids):
        # ``memory_peak_bytes`` on the result line is the process's, and
        # the reference beside the engine sets it: say what the engine
        # alone had reached (weights, slots, pool, every program
        # ``warm()`` ran, the probes), which is what the window repeats
        served = _peak_bytes()
        out = super().logits(conf, params, ids)
        self.session = session_margin(self.arch, conf, params, ids)
        print(f"[bench] device memory: peak {served} bytes before the "
              f"reference ran (the engine, its warmed programs and the "
              f"probes), {_peak_bytes()} after it and the session probe, "
              f"of {_peak_bytes('bytes_limit')}", flush=True)
        return out


def _peak_bytes(key: str = "peak_bytes_in_use") -> int:
    import jax
    return int((jax.devices()[0].memory_stats() or {}).get(key, 0))


def session_margin(arch, conf: dict, params, ids) -> dict | None:
    """Turn 2 of the probe's session: ``ids`` (the probe and all but the
    last token of its cold answer) come back with ``SESSION_NEW`` new
    tokens.  The engine must find the probe's chain in the pool,
    re-attach its blocks and the state snapshot at the probe's last
    prompt block edge, and re-prefill the tail (the rest of the prompt,
    the answer, the new tokens); its ``PROBE_NEW`` tokens are judged
    against the reference's ONE pass over the whole history, under the
    nearest honest routing.  ``{"margin", "hit", "skipped",
    "reprefilled"}``."""
    import jax.numpy as jnp

    engine = hybrid._TraceWindow.engine
    rng = np.random.default_rng(int(np.asarray(ids).sum()) % (1 << 31))
    history = np.asarray(ids)[0].tolist() + rng.integers(
        1, conf["vocab_size"], SESSION_NEW).tolist()
    before = engine.stats()
    answer = [int(t) for t in engine.submit(
        np.asarray(history, np.int32), hybrid.PROBE_NEW,
        session="bench-session-probe").result(300.0)]
    after = engine.stats()
    if len(answer) != hybrid.PROBE_NEW:
        return None
    full = jnp.asarray([history + answer[:-1]], jnp.int32)
    ref = arch.reference(conf, params, full)
    worst = 0.0
    for j, token in enumerate(answer):
        found = arch.tie_aware_shortfall(
            conf, params, full, ref, len(history) - 1 + j, token,
            limit=hybrid.MARGIN_TOLERANCE_SIGMA, delta=hybrid.TIE_DELTA)
        worst = max(worst, found["shortfall"])
    out = {"margin": worst,
           "hit": after["kv_prefix_hits"] - before["kv_prefix_hits"],
           "skipped": (after["kv_prefill_tokens_skipped"]
                       - before["kv_prefill_tokens_skipped"]),
           "reprefilled": (after["kv_state_reprefill_tokens"]
                           - before["kv_state_reprefill_tokens"])}
    print(f"[bench] session probe: turn 2 of {len(history)} tokens "
          f"re-attached {out['skipped']} from the pool (hits {out['hit']}), "
          f"re-prefilled {len(history) - out['skipped']} "
          f"({out['reprefilled']} of them pooled rows behind the snapshot); "
          f"its {len(answer)} tokens lie at most {worst:.4f} sigma under the "
          f"reference's one pass over the whole history (tolerance "
          f"{hybrid.MARGIN_TOLERANCE_SIGMA})", flush=True)
    return out


def checks_of(arch, conf: dict, counters: dict, block: dict | None,
              long: dict | None, probe_edges: list[dict],
              margin: dict | None, session: dict | None) -> dict:
    routed = (conf["num_experts_per_tok"] * arch.sparse_layers(conf)
              * counters.get("moe_tokens", 0))
    computed = (probe_edges[1]["moe_assignments"]
                - probe_edges[0]["moe_assignments"]
                if len(probe_edges) == 2 else None)
    recount = block["held_pairs"] if block else None
    print(f"[bench] held pairs on the cold probe: the engine computed "
          f"{computed}, the host recounts {recount} (tolerance "
          f"{HELD_PAIRS_TOLERANCE})", flush=True)
    block_size = conf["run"]["kv_block"]
    return {
        "served_margin": margin is not None and
        margin["cold"] <= hybrid.MARGIN_TOLERANCE_SIGMA,
        "pooled_margin": margin is not None and
        margin["pooled"] <= hybrid.MARGIN_TOLERANCE_SIGMA,
        # the second turn resumed from the pool, at a block edge, and
        # its tokens are the reference's
        "session_resumed": session is not None and session["hit"] == 1
        and session["skipped"] > 0 and session["skipped"] % block_size == 0,
        "session_margin": session is not None and
        session["margin"] <= hybrid.MARGIN_TOLERANCE_SIGMA,
        "nothing_dropped": counters.get("moe_prefill_drops", -1) == 0,
        "every_token_routed": routed > 0 and
        counters.get("moe_assignments_routed", -1) == routed,
        "held_pairs_recount": bool(
            computed and recount and abs(computed - recount)
            <= HELD_PAIRS_TOLERANCE * recount),
        **block_checks(block, long)}


class _Spans(hybrid._Spans):
    """``serve_hybrid``'s taps, and (``serve_mla._Spans``' reasons) the
    reuse family warmed for the chain depths this traffic reaches and
    the chunk lane's last-chunk program for every bucket a remainder
    can land on: a first turn is a context and a tool turn."""

    chain_blocks: frozenset = frozenset()

    def tap_engine(self, engine) -> None:
        super().tap_engine(engine)
        engine.warm = functools.partial(
            engine.warm, chain_blocks=sorted(self.chain_blocks),
            chunk_finals=True)


def chain_depths(conf: dict, traffic: dict, seconds: float) -> frozenset:
    """Depths, in blocks, of the chains this traffic re-attaches: the
    probe's, and every session's from its first turn to its last."""
    gen = importlib.import_module(f"generators.{traffic['generator']}")
    block = conf["run"]["kv_block"]
    shapes = gen.shapes(traffic, seconds, block)
    lo = min(shapes["doc_lens"]) // block
    hi = shapes["max_total"] // block + 1
    return frozenset({traffic["probe_tokens"] // block, *range(lo, hi + 1)})


class _TraceWindow(hybrid._TraceWindow):
    """``serve_hybrid``'s window, which also reads the device time of
    the tiled chunk attention (``trace["scopes"]["attn/prefix_chunk"]``).
    ``trace_reduce`` keys an op by its HLO name, the capture carries no
    scope (tried on the chip, PR 52: an event's stats are its times
    alone), and an XLA loop has no name of its own: its event is
    ``%while.N = (the carried tuple's types) while(...)`` and spans its
    body.  So the loop is known by what it carries: the float32 output
    accumulator ``[lanes, KV heads, group, queries, head size]`` of
    ``ops/decode_attention.prefix_chunk_attention`` (``carry``, a
    pattern made of the configuration's three widths; no other loop of
    the stack carries five dimensions).  A kernel in the loop's place
    has a name: ``layer_metrics/prefix_attend_roofline`` reads it from
    ``trace["ops"]`` and this finds nothing."""

    carry = None

    def reduce(self) -> dict:
        import trace_reduce
        out = super().reduce()
        secs = loop_seconds(trace_reduce.find_xplane(self.dir), self.carry)
        if secs:
            out.setdefault("scopes", {})["attn/prefix_chunk"] = secs
        return out


def carry_pattern(conf: dict):
    import re
    hk = conf["num_key_value_heads"]
    return re.compile(rf"^%while[.\d]* = \(.*f32\[\d+,{hk},"
                      rf"{conf['num_attention_heads'] // hk},\d+,"
                      rf"{conf['head_dim']}\]")


def loop_seconds(path: str, carry) -> float:
    """Seconds of the first device's ``while`` events inside the traced
    window whose carried tuple matches ``carry`` (the union of their
    intervals)."""
    import trace_reduce
    from jax.profiler import ProfileData

    if carry is None:
        return 0.0
    data = ProfileData.from_file(path)
    window, device = None, None
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW_SPAN:
                        window = (float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns))
        elif trace_reduce.DEVICE_PLANE.match(plane.name) and (
                device is None or plane.name < device.name):
            device = plane
    if device is None:
        return 0.0
    found = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
             for line in device.lines if line.name == trace_reduce.OPS_LINE
             for ev in line.events if carry.match(ev.name)]
    if window is not None:
        found = trace_reduce.clip(found, *window)
    return trace_reduce.total(trace_reduce.union(found)) * 1e-9


def run(cell: dict, conf: dict, traffic: dict, args, t_start: float) -> dict:
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    model, ref = _Model(arch), _Reference(arch)
    sys.modules["model"], sys.modules["reference"] = model, ref
    # serve.py's own comparison of the probe's tokens knows one routing:
    # served_margin makes both comparisons at serve_hybrid's limit, and
    # serve.py keeps the rest (both answers whole, the pooled one a hit)
    serve.MARGIN_TOLERANCE_SIGMA = float("inf")
    serve.PROBE_NEW = hybrid.PROBE_NEW
    spans, window = _Spans, _TraceWindow
    spans.probe_tokens = traffic["probe_tokens"]
    spans.chain_blocks = chain_depths(conf, traffic, float(args.seconds))
    window.carry = carry_pattern(conf)
    serve._Spans, common.TraceWindow = spans, window
    result = serve.run(cell, conf, traffic, args, t_start)
    counters = result["counters"]
    checks = checks_of(arch, conf, counters, ref.block, model.long,
                       spans.probe_edges, ref.margin, ref.session)
    sizes = window.engine.stats()           # levels, not differences
    turns = len(result["records"])
    print(f"[bench] deltagqa checks {checks}: {turns} turns finished in the "
          f"window; {counters.get('moe_assignments_routed')} pairs routed "
          f"for {counters.get('moe_tokens')} tokens, "
          f"{counters.get('moe_assignments')} on held experts, "
          f"{counters.get('moe_prefill_drops')} drops; a slot holds "
          f"{sizes.get('kv_slot_bytes_global')} bytes of K and V rows and "
          f"{sizes.get('kv_slot_bytes_state')} of delta-rule state whatever "
          f"max_len is; {sizes.get('kv_state_snapshots')} state snapshots "
          f"held, {sizes.get('kv_state_snapshot_skips')} skipped, "
          f"{sizes.get('kv_blocks_used')} blocks used, "
          f"{sizes.get('kv_sessions')} sessions pinned; in the window "
          f"{counters.get('kv_prefix_hits')} chains re-attached, "
          f"{counters.get('kv_prefix_misses')} cold, "
          f"{counters.get('kv_evictions')} blocks evicted, "
          f"{counters.get('kv_commit_skips')} commits cut short, "
          f"{counters.get('kv_state_reprefill_tokens')} pooled tokens "
          f"re-prefilled of {counters.get('kv_prefill_tokens')} prompt "
          f"tokens ({counters.get('kv_prefill_tokens_skipped')} skipped); "
          f"the chunk calls read {counters.get('kv_prefill_rows_read')} "
          f"rows for {counters.get('kv_prefill_rows_live')} live; "
          f"tolerances: KDA mixers {MIXER_TOLERANCE}, GQA mixers "
          f"{ATTENTION_TOLERANCE}, through the cache chunk "
          f"{LONG_CHUNK_TOLERANCE} step {LONG_STEP_TOLERANCE}, expert "
          f"layers {EXPERT_TOLERANCE}, their held experts "
          f"{ROUTED_TOLERANCE}, the carried state {STATE_TOLERANCE}, block "
          f"logits {BLOCK_TOLERANCE_SIGMA} and through the cache "
          f"{CACHE_TOLERANCE_SIGMA} sigma", flush=True)
    result["correct"] = bool(result["correct"] and all(checks.values()))
    if len(window.edges) == 2:
        first, last = window.edges
        counters["trace_span_counters"] = {
            k: last[k] - first[k] for k in first
            if k.startswith(("moe_", "ssm_", "decode_kv_tokens_",
                             "kv_prefill_", "kv_state_reprefill",
                             "prefill_chunks", "first_tokens"))}
    return result
