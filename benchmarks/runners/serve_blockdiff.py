"""The serving cell of a model that generates by diffusion over blocks
(``archs/sdar_moe.py``): every token comes out of passes of ``L``
positions over the cache, a pass yields 0 or ``L`` tokens a slot, and
prompts prefill under the block-causal mask.

It hands over to ``runners/serve.py`` as ``runners/serve_arch.py`` does
(the configuration's ``run.arch`` names the module under ``archs/`` that
is both ``model`` and ``reference``; one process runs one cell).  The
engine ``serve.py`` builds IS the block engine: the generation loop's
settings ride on the configuration ``archs/sdar_moe.transformer_config``
makes.  What this file has to put between them, since ``serve.py`` is
not this PR's to edit: its warm-up reckons prompt buckets and pool-commit
sizes from whole prompts, where a block engine prefills ``floor(P / L) *
L`` rows and commits whole blocks (``_buckets_for``, ``_warm_commits``
below); and its margin reads ``reference.logits`` as next-token logits,
where a block model's logits AT a position are that position's
(``_Reference.logits`` hands it, for each served token, the reference's
row of the pass that unmasked it).

What ``correct`` rests on, beside ``runners/serve.py``'s own checks
(the probe's tokens whole, the pooled probe equal to the cold one up to
a near-tie, every window request answered):

(a) ``logit_check``: the engine's OWN programs against the reference's
    ONE full forward under ``M``, three prompts LIVE TOGETHER in lanes
    apart of a cache of the engine's own shape (16 slots in the cell):
    each prompt's ``P0`` rows prefilled (one prompt through the chunk
    lane's start / mid / last programs, one with ``P % L != 0`` whose
    tail opens the first block, one of two blocks) and placed by the
    engine's compiled insert; then the pass program's forward over all
    lanes at once, each lane at its own index: for each of two blocks a
    denoise pass with half the block masked and the commit pass that
    overwrites its rows, then a half-masked pass of a third.  The logits
    of the two commits and of the last pass, ``3 L`` rows a prompt, in
    units of the logits' own spread, each prompt against its own limit
    (``LOGIT_TOLERANCE_SIGMA``); and the program's expert layers ALONE
    on the reference's inputs (``EXPERT_TOLERANCE``).
(b) the engine's answers against the reference's loop FED the engine's
    own tokens (``served_passes``), for the cold probe and for a BURST
    of requests served together through the timed pass program
    (``served_together``: six at once, prompts of every ``P % L``,
    answers of different lengths, one no multiple of ``L``, and two more
    that take slots beside answers in progress): at every denoise pass
    the token the engine unmasked lies within ``MARGIN_TOLERANCE_SIGMA``
    of the reference's best at that position, and the position it
    unmasked is one the reference ranks within ``RANK_TOLERANCE`` of the
    last it would have taken (a tie by rounding is no error; ``within``:
    a fifteenth of the unmaskings, two of a probe's 17, may lie past
    the tolerance, a swapped expert's rows, none past the gross limit:
    a block that reached another slot's request is whole sigmas down).
(c) the counters around the cold probe (``probe_counted``): live
    (slot, pass) pairs, commits, positions unmasked and tokens delivered
    are what ``L``, the denoising steps and the prompt's tail say.

``benchmarks/tests/chip_sdar_variants.py`` puts the deliberately wrong
programs through (a); PERF.md section 6 (PR 48) has the readings.
"""

from __future__ import annotations

import importlib
import sys

import numpy as np

from . import common, serve, serve_arch

# (b), the token: how far the reference's logit of the token the engine
# unmasked lies under the reference's best at that position of that
# pass, in standard deviations of the row (``serve.py``'s measure of the
# name, which this runner hands those rows).  With random weights the
# top logits are closer than bf16's error, so the argmax flips between
# near-ties; and a row whose 8th and 9th expert swap on the rounded
# input (``serve_arch.py``'s reason, here over 128 experts and a
# vocabulary of 151,936 whose top is flat at a masked position) is
# moved by a tenth of a sigma whole: of the 17 unmaskings of a probe
# most read 0.00 and ONE may read 0.1-0.4 (five probes on the chip:
# 0.070, 0.000, 0.378, 0.000, 0.134 at the largest; PERF.md section 6,
# PR 48).  A wrong cache row, a causal mask or K/V kept from a denoise
# pass moves EVERY row (check (a): every row of a prompt 0.12-0.45), so
# the judge allows ``OUTLIERS`` unmaskings of a probe past the tolerance
# and none past ``GROSS_SIGMA`` (a random token is about 4 down); of the
# burst's 288 (``within``) a fifteenth, 19: the engine as it is read 0-3
# past (eight runs; positions 1-5 past ``RANK_TOLERANCE``), the engine
# whose commits do not write 44 (positions 33): my chip run 9, PR 48 (of
# run 8's burst of 239: 2 and 3, positions 5 and 6, against 24 and 28).
MARGIN_TOLERANCE_SIGMA = 0.15
GROSS_SIGMA = 1.0
OUTLIERS = 2

# (b), the position: log-confidence (the row's best logit less its
# log-sum-exp) of the position the engine unmasked, under the n-th best
# of the reference's own ranking among the masked positions (n = the
# positions a pass unmasks), in nats.  The positions' confidences differ
# by tenths of a nat on random weights and bf16 moves one by hundredths:
# a flip between two that close is rounding, a choice a whole rank off
# is a wrong rule (it is off in every pass with two or more masked
# positions, 13 of a probe's 17).  The same allowance for a swapped
# expert's row, and none past ``GROSS_NATS``.
RANK_TOLERANCE = 0.08
GROSS_NATS = 1.0


def within(shortfalls: list, tolerance: float, gross: float) -> bool:
    """All but ``OUTLIERS`` (a fifteenth, of 45 or more) of
    ``shortfalls`` within ``tolerance``, and every one within ``gross``;
    False for none at all."""
    worst = sorted(shortfalls, reverse=True)
    allowed = outliers(len(worst))
    return bool(worst) and worst[0] <= gross and (
        len(worst) <= allowed or worst[allowed] <= tolerance)


def outliers(n: int) -> int:
    return max(OUTLIERS, n // 15)


# (a): at each of the 3 L rows of a prompt the root mean square over the
# vocabulary of (program - reference), in standard deviations of the
# reference's logits there; a prompt's MEDIAN row (a row whose 8th and
# 9th expert swap on the rounded input is far out and honest,
# ``serve_arch.py``: 3.6-6.3% of (token, layer) pairs; here 9 of 36 rows
# read 0.04-0.10 beside 0.009-0.018).  Six layers of bf16 rounding
# through the cache.  Each prompt has its OWN limit, between its own
# honest and wrong readings, because what a wrong mask or a wrong row
# moves is a share of what a row sees: at the prompt of two blocks it is
# most of it, behind 200-330 prefilled rows a tenth.  In the order of
# ``logit_check``'s prompts (chunk lane, tail of 3, two blocks):
# the program as it is 0.007-0.015 at each, and now and then, at any of
# them, the median itself is a swapped expert's row: 0.026, 0.030, 0.044
# and 0.078 once each in 48 readings of the first two, 0.048, 0.052 and
# 0.066 of the third (a single such row has read up to 0.17; half of a
# prompt's twelve rows that far out at once has not been seen); a causal
# mask in place of M in every multi-token program 0.15-0.20 / 0.15-0.20 /
# 0.38-0.41 with EVERY row at 0.15 or more, in the chunk lane's mid and
# last programs ALONE 0.167 and 0.187 / honest / honest (only the first
# prompt runs them: one limit of 0.15 for all sat ON that reading); a
# commit whose K/V is not written, so that a denoise pass's stay,
# 0.06-0.13 / 0.07-0.15 / 0.39-0.45 (PERF.md section 6, PR 48, my chip
# runs 1-9: one lane alone in runs 1-7, three live together in a cache
# of 4 or 16 lanes since, the same readings).  Each limit is 1.5-2.3
# times its largest honest reading and 1.4-2.6 times under the smallest
# reading of a wrong mask.  bfloat16 scores are NOT seen by it (the
# program with them 0.019 / 0.021, the reference with them against
# itself 0.003-0.007: a score's rounding moves a weight by 0.4%, a
# hundred keys average that away under the output's own rounding); the
# limit that a lower precision fails is the next one.
LOGIT_TOLERANCE_SIGMA = (0.12, 0.12, 0.15)

# (a): the program's expert layers ALONE, each fed the reference's own
# input (``archs/sdar_moe.expert_error``, the median over tokens and
# layers): one layer of bf16 rounding.  The limit lies between the
# program's reading, 0.00508-0.00510 (five readings of three seeds), and
# the REFERENCE's own expert layers with the expert weights rounded to
# int8 per output channel, the nearest precision below the stated
# bfloat16: 0.00956 / 0.00961, not correct (the program with int8
# experts 0.01084 / 0.01088).  ``serve_arch.EXPERT_TOLERANCE``'s
# construction and, the kernel being the same, its readings to the digit.
EXPERT_TOLERANCE = 0.007

PROBE_NEW = serve.PROBE_NEW

# (b)'s burst: (prompt tokens past the probe's length, answer tokens).
# The first ``AT_ONCE`` go in together, the rest when the first of them
# is done: every ``P % L``, answers that end in different dispatches,
# one that is no multiple of ``L`` (its last block cut at emission)
BURST = ((0, 24), (1, 32), (2, 40), (3, 30), (5, 64), (4, 48), (6, 16),
         (7, 24))
AT_ONCE = 6
# at least half of the burst's unmaskings happen in a dispatch with this
# many slots live (with all but one of the slots, where there are fewer)
LIVE_TOGETHER = 4


class _Spans(serve_arch._Spans):
    """``serve.py``'s tap, and around each probe (a request of
    ``probe_tokens`` tokens for ``PROBE_NEW``): the engine's pass log
    switched on for it and its counters read at both ends."""

    engine = None
    probe_tokens = 0
    probes: list = []           # [future, stats before, stats after]

    def tap_engine(self, engine) -> None:
        super().tap_engine(engine)
        _Spans.engine = engine
        tapped = engine.submit

        def submit(prompt, max_new_tokens, *a, **kw):
            n = np.asarray(prompt).reshape(-1).shape[0]
            if n != _Spans.probe_tokens or max_new_tokens != PROBE_NEW:
                return tapped(prompt, max_new_tokens, *a, **kw)
            if engine.pass_log is None:
                engine.pass_log = {}
            entry = [None, engine.stats(), None]
            fut = entry[0] = tapped(prompt, max_new_tokens, *a, **kw)
            fut.add_done_callback(
                lambda _f: entry.__setitem__(2, engine.stats()))
            _Spans.probes.append(entry)
            return fut

        engine.submit = submit


def served_passes(arch, conf: dict, params, prompt: list, log: list,
                  gen: dict) -> dict:
    """Check (b).  ``log``: the engine's records of ONE request's passes
    in order (``ContinuousBatcher.pass_log``).  The reference's loop is
    FED the engine's decisions: before each denoise pass it holds what
    the engine held (the rows committed so far, the block with its
    masks), and its logits at the block judge what the engine unmasked
    next.  A block's denoise passes are one batch of the reference (a
    first block's fewer filled up by a repeat), and the sequence is
    padded with whole blocks to a multiple of 128 (under ``M`` no row
    sees a later block): one program a length class, not one a block.
    Returns ``{"rows" [answer tokens, V]: for each token after the
    prompt the reference's row of the pass that unmasked it,
    "token_shortfall" [unmaskings], "rank_shortfall" [...], "live"
    [...]: the slots live in the dispatch of each}``."""
    import jax.numpy as jnp

    L, mask_id = gen["block_length"], gen["mask_id"]
    n_unmask = -(-L // gen["denoising_steps"])
    P0 = len(prompt) // L * L
    given = len(prompt) - P0
    if not log or log[0]["masked"] != [i >= given for i in range(L)]:
        raise ValueError("the pass log does not start at the request's "
                         "first block")
    done = list(prompt[:P0])
    out = {"rows": {}, "token_shortfall": [], "rank_shortfall": [],
           "live": []}
    block: list = []            # (pass, the pass after it) of the open block
    for e, nxt in zip(log, log[1:] + [None]):
        if not e["commit"]:
            if nxt is not None:         # else the request ended in the block
                block.append((e, nxt))
            continue
        batch = ()
        if block:
            pad = -(len(done) + L) % 128
            ids = [done + [mask_id if m else t
                           for t, m in zip(d["tok"], d["masked"])] + [1] * pad
                   for d, _ in block]
            ids += ids[:1] * (-(-L // n_unmask) - len(ids))
            batch = np.asarray(arch.logits(
                conf, params, jnp.asarray(ids, jnp.int32), block_length=L,
                rows=slice(len(done), len(done) + L)))
        for (d, after), row in zip(block, batch):
            best = row.max(-1)
            lse = np.log(np.exp(row - best[:, None]).sum(-1)) + best
            logconf = best - lse
            ranked = sorted((logconf[i] for i in range(L) if d["masked"][i]),
                            reverse=True)
            floor = ranked[min(n_unmask, len(ranked)) - 1]
            for i in range(L):
                if not d["masked"][i] or after["masked"][i]:
                    continue
                t = after["tok"][i]
                out["token_shortfall"].append(
                    float((best[i] - row[i, t]) / row[i].std()))
                out["rank_shortfall"].append(
                    float(max(0.0, floor - logconf[i])))
                out["live"].append(d["live"])
                out["rows"][len(done) - P0 + i - given] = row[i]
        done += e["tok"]
        block = []
    return out


def served_together(engine, arch, conf: dict, params, gen: dict,
                    seed: int, n_prompt: int) -> dict:
    """Check (b) on ``BURST``: requests the engine serves TOGETHER,
    through the pass program and the cache the window is timed on (the
    probes are alone on an idle engine).  ``served_passes``' lists, the
    requests' joined, and ``"whole"``: every answer of its length."""
    from concurrent.futures import FIRST_COMPLETED, wait

    rng = np.random.default_rng([seed % (1 << 63), 49])
    asks = [(rng.integers(1, conf["vocab_size"], n_prompt + extra).tolist(),
             new) for extra, new in BURST]
    engine.pass_log = logs = {}
    try:
        futs = [engine.submit(np.asarray(prompt, np.int32), new)
                for prompt, new in asks[:AT_ONCE]]
        wait(futs, timeout=600.0, return_when=FIRST_COMPLETED)
        futs += [engine.submit(np.asarray(prompt, np.int32), new)
                 for prompt, new in asks[AT_ONCE:]]
        answers = [np.asarray(f.result(timeout=600.0)).tolist()
                   for f in futs]
    finally:
        engine.pass_log = None
    out = {"token_shortfall": [], "rank_shortfall": [], "live": [],
           "together": min(LIVE_TOGETHER, len(engine._slots) - 1),
           "whole": all(len(a) == new
                        for a, (_, new) in zip(answers, asks))}
    for fut, (prompt, _) in zip(futs, asks):
        one = served_passes(arch, conf, params, prompt, logs[fut], gen)
        for k in ("token_shortfall", "rank_shortfall", "live"):
            out[k] += one[k]
    return out


def logit_check(engine, arch, conf: dict, params, seed: int, *,
                prompts=None, commit_writes: bool = True) -> dict:
    """Check (a), on the engine's own programs (module docstring): the
    prompts live together in lanes apart of a fresh cache of the
    engine's own shape.  ``commit_writes`` False is one of the wrong
    programs of ``chip_sdar_variants.py``: a commit that moves the index
    without its pass, so that the K/V of the denoise pass before it stay
    (the others are wrong engines handed in).  Returns
    ``{"logit_error_sigma" [rows], "logit_error_by_prompt" [prompts]
    (each prompt's median row), "expert_error" [...]}``."""
    import jax
    import jax.numpy as jnp

    gen = arch.generation(conf)
    L, mask_id = gen["block_length"], gen["mask_id"]
    C = engine._chunk_tokens
    rng = np.random.default_rng([seed % (1 << 63), 48])
    V = conf["vocab_size"]
    if prompts is None:
        # one through the chunk lane (a mid chunk and a last one), one
        # whose tail (3 tokens) opens the first block, and one of two
        # blocks: there the rows a causal mask hides from a block's
        # earlier positions are most of what they see
        prompts = [rng.integers(1, V, C + 17 * L + 2).tolist(),
                   rng.integers(1, V, 50 * L + 3).tolist(),
                   rng.integers(1, V, 2 * L + 1).tolist()]
    key = jax.random.key(0)
    S, n = len(engine._slots), len(prompts)
    lanes = [int(i) for i in np.linspace(S - 1, 0, n)]
    if len(set(lanes)) < n:
        raise ValueError(f"{n} prompts do not fit {S} slots")
    cache, state = engine._fresh_cache(S), engine._block_state(S)
    heads, tails = [], []
    for lane, prompt in zip(lanes, prompts):
        P0 = len(prompt) // L * L
        head, tail = prompt[:P0], prompt[P0:]
        if P0 > C:
            slab, sown = engine._chunk_start()
            off = 0
            while P0 - off > C:
                slab, sown = engine._chunk_mid_fn(C)(
                    engine._params, slab,
                    jnp.asarray([head[off:off + C]], jnp.int32), sown)
                off += C
            Pb = engine._bucket(P0 - off)
            ids = np.zeros((1, Pb), np.int32)
            ids[0, :P0 - off] = head[off:]
            slab, *_ = engine._chunk_final_fn(Pb)(
                engine._params, slab, jnp.asarray(ids),
                jnp.asarray([P0 - off], jnp.int32), sown, key, None)
        else:
            Pb = engine._bucket(P0)
            ids = np.zeros((1, Pb), np.int32)
            ids[0, :P0] = head
            slab, *_ = engine._prefill_fn(Pb, 1)(
                engine._params, jnp.asarray(ids),
                jnp.asarray([P0], jnp.int32), key, None)
        # the engine's compiled insert: the slab into its lane, the
        # lane's index at the rows prefilled
        cache, state = engine._insert_jit(
            cache, state, slab, jnp.asarray([lane], jnp.int32),
            jnp.asarray([P0], jnp.int32), engine._block_state(1))
        heads.append(head)
        tails.append(tail)

    fwd = jax.jit(engine._pass_forward, donate_argnums=(1,))
    on = np.zeros((S,), bool)
    on[lanes] = True
    index = np.zeros((S,), np.int32)
    index[lanes] = [len(h) for h in heads]

    def a_pass(cache, tok, masked, moved):
        """One pass of every lane at once; the lanes' indices then at
        ``index + moved``."""
        toks, flags = np.zeros((S, L), np.int32), np.zeros((S, L), bool)
        toks[lanes], flags[lanes] = tok, masked
        lg, mut = fwd(engine._params, cache, jnp.asarray(toks),
                      jnp.asarray(flags), jnp.asarray(on))
        at = index + moved * on     # an array a leaf: the cache is donated
        return (np.asarray(lg)[lanes], jax.tree.map(
            lambda leaf: jnp.asarray(at) if leaf.ndim == 1 else leaf,
            mut["cache"]))

    # the last two positions masked: a denoise pass's K/V
    half = [i >= L - 2 for i in range(L)]
    blocks, got = [[] for _ in prompts], [[] for _ in prompts]
    for b in range(3):
        tok = [(t if b == 0 else []) + rng.integers(
            1, V, L - (len(t) if b == 0 else 0)).tolist() for t in tails]
        lg, cache = a_pass(cache, tok, [half] * n, b * L)
        if b < 2 and commit_writes:
            lg, cache = a_pass(cache, tok, [[False] * L] * n, b * L)
        cache = jax.tree.map(
            lambda leaf: leaf + L * on if leaf.ndim == 1 else leaf,
            cache) if b < 2 else cache
        for i in range(n):
            if b == 2 or commit_writes:
                got[i].append(lg[i])
            blocks[i] += tok[i] if b < 2 else [
                mask_id if m else t for t, m in zip(tok[i], half)]
    del cache
    errs, expert = [], []
    for head, block, have in zip(heads, blocks, got):
        seq = jnp.asarray([head + block], jnp.int32)
        ref = arch.reference(
            conf, params, seq, block_length=L,
            rows=slice(len(head) + (0 if commit_writes else 2 * L), None))
        want = np.asarray(ref["logits"][0])
        have = np.concatenate(have)
        errs.append(np.sqrt(np.mean(np.square(have - want), -1))
                    / want.std(-1))
        expert.append(arch.expert_error(engine._dcfg, params, ref))
    return {"logit_error_sigma": np.concatenate(errs),
            "logit_error_by_prompt": [float(np.median(e)) for e in errs],
            "expert_error": np.concatenate(expert)}


def logits_within(by_prompt: list) -> bool:
    """Check (a)'s verdict: each prompt's median row within its limit
    (prompts beyond the three of the cell take the last one's)."""
    limits = LOGIT_TOLERANCE_SIGMA + LOGIT_TOLERANCE_SIGMA[-1:] * len(
        by_prompt)
    return all(e <= lim for e, lim in zip(by_prompt, limits))


class _Reference:
    """What ``runners/serve.py`` sees as ``reference``.  Its one call
    comes after both probes, with the engine idle and the window not yet
    open: the place for (a) and (b)."""

    def __init__(self, arch, gen: dict, seed: int):
        self.arch, self.gen, self.seed = arch, gen, seed
        self.served = self.together = self.block = None

    def logits(self, conf, params, ids):
        ids = np.asarray(ids)[0].tolist()
        n_prompt, engine = _Spans.probe_tokens, _Spans.engine
        self.served = served_passes(
            self.arch, conf, params, ids[:n_prompt],
            engine.pass_log[_Spans.probes[0][0]], self.gen)
        self.together = served_together(engine, self.arch, conf, params,
                                        self.gen, self.seed, n_prompt)
        self.block = logit_check(engine, self.arch, conf, params, self.seed)
        # serve.py reads row n_prompt - 1 + j as the judge of answer
        # token j
        rows = self.served["rows"]
        out = np.zeros((1, len(ids), conf["vocab_size"]), np.float32)
        for j in range(len(ids) + 1 - n_prompt):
            if j in rows:
                out[0, n_prompt - 1 + j] = rows[j]
        return out


def probe_counted(gen: dict, n_prompt: int, probe: list) -> bool:
    """Check (c) on the counters around the cold probe."""
    if not probe or probe[2] is None:
        return False
    L = gen["block_length"]
    n_unmask = -(-L // gen["denoising_steps"])
    given = n_prompt % L
    blocks = -(-(given + PROBE_NEW) // L)
    passes = blocks + -(-(L - given) // n_unmask) \
        + (blocks - 1) * -(-L // n_unmask)
    d = {k: probe[2][k] - probe[1][k] for k in probe[1]
         if k.startswith("blockdiff_")}
    want = {"blockdiff_slot_passes": passes,
            "blockdiff_blocks_committed": blocks,
            "blockdiff_tokens_unmasked": blocks * L - given,
            "blockdiff_given_tokens": given,
            "blockdiff_tokens_delivered": PROBE_NEW}
    got = {k: d.get(k) for k in want}
    print(f"[bench] the cold probe's passes: counted {got}, expected "
          f"{want}", flush=True)
    return got == want


def _largest(values: list) -> str:
    top = [round(v, 4) for v in sorted(values)[-3:]]
    return f"max {max(values):.4f}, largest three {top}"


def run(cell: dict, conf: dict, traffic: dict, args, t_start: float) -> dict:
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    gen = arch.generation(conf)
    L = gen["block_length"]
    ref = _Reference(arch, gen, args.seed)
    sys.modules["model"], sys.modules["reference"] = arch, ref
    # serve.py's own margin is the largest row's: the gross limit there,
    # the judge with its allowance below
    serve.MARGIN_TOLERANCE_SIGMA = GROSS_SIGMA
    _Spans.probe_tokens = traffic["probe_tokens"]
    serve._Spans, common.TraceWindow = _Spans, serve_arch._TraceWindow
    # a block engine prefills whole blocks of a prompt, and commits
    # whole blocks of the answer to the pool
    buckets_for, warm_commits = serve._buckets_for, serve._warm_commits
    serve._buckets_for = lambda lens, chunk, max_len: buckets_for(
        [n // L * L for n in lens], chunk, max_len)
    serve._warm_commits = lambda engine, counts: warm_commits(
        engine, sorted(set(counts) | {c + 1 for c in counts}))
    result = serve.run(cell, conf, traffic, args, t_start)
    counters = result["counters"]
    served, burst, block = ref.served, ref.together, ref.block
    routed = serve_arch.expert_checks(conf, counters, None)
    ran = served is not None and burst is not None and block is not None
    checks = {
        "served_tokens": ran and within(
            served["token_shortfall"], MARGIN_TOLERANCE_SIGMA, GROSS_SIGMA),
        "served_positions": ran and within(
            served["rank_shortfall"], RANK_TOLERANCE, GROSS_NATS),
        "together_tokens": ran and burst["whole"] and within(
            burst["token_shortfall"], MARGIN_TOLERANCE_SIGMA, GROSS_SIGMA),
        "together_positions": ran and within(
            burst["rank_shortfall"], RANK_TOLERANCE, GROSS_NATS),
        "together_live": ran and 2 * sum(
            n >= burst["together"] for n in burst["live"]) >= len(
                burst["live"]),
        "cache_logits": ran and logits_within(
            block["logit_error_by_prompt"]),
        "expert_layers": ran and bool(
            np.median(block["expert_error"]) <= EXPERT_TOLERANCE),
        "probe_counted": probe_counted(
            gen, traffic["probe_tokens"],
            _Spans.probes[0] if _Spans.probes else None),
        # serve_arch's two exact counters (its two limits are (a)'s here)
        **{k: routed[k] for k in ("nothing_dropped", "every_token_routed")},
    }
    print(f"[bench] blockdiff checks {checks}", flush=True)
    if ran:
        past = sum(v > MARGIN_TOLERANCE_SIGMA
                   for v in burst["token_shortfall"])
        print(f"[bench] the cold probe's "
              f"{len(served['token_shortfall'])} unmaskings: token "
              f"shortfall {_largest(served['token_shortfall'])} sigma "
              f"(tolerance {MARGIN_TOLERANCE_SIGMA} for all but "
              f"{OUTLIERS}, {GROSS_SIGMA} for all), position shortfall "
              f"{_largest(served['rank_shortfall'])} nats (tolerance "
              f"{RANK_TOLERANCE}, {GROSS_NATS}); {len(BURST)} requests "
              f"served together, {len(burst['live'])} unmaskings, "
              f"{sum(n >= burst['together'] for n in burst['live'])} of "
              f"them with {burst['together']} or more slots live (median "
              f"{int(np.median(burst['live']))}): token shortfall "
              f"{_largest(burst['token_shortfall'])} sigma, {past} past "
              f"the tolerance of {outliers(len(burst['live']))} allowed, "
              f"position shortfall {_largest(burst['rank_shortfall'])} "
              f"nats, "
              f"{sum(v > RANK_TOLERANCE for v in burst['rank_shortfall'])}"
              f" past; through the cache on the engine's own programs, "
              f"three lanes live in a cache of {counters['slots']}, "
              f"logits: a prompt's median row "
              f"{[round(v, 5) for v in block['logit_error_by_prompt']]} "
              f"(tolerances {list(LOGIT_TOLERANCE_SIGMA)}), the largest "
              f"row {block['logit_error_sigma'].max():.5f} sigma over "
              f"{block['logit_error_sigma'].size} rows; expert layers "
              f"alone, median {np.median(block['expert_error']):.5f} "
              f"(tolerance {EXPERT_TOLERANCE})", flush=True)
    result["correct"] = bool(result["correct"] and all(checks.values()))
    edges = serve_arch._TraceWindow.edges
    if len(edges) == 2:
        first, last = edges
        counters["trace_span_counters"] = {
            k: last[k] - first[k] for k in first
            if k.startswith(("moe_", "blockdiff_", "decode_kv_tokens_",
                             "kv_prefill_tokens"))
            and isinstance(first[k], (int, float))}
    return result
