"""Open-loop replay of a fixed cyclic trace.

``N = round(rate * seconds)`` requests.  Prompt lengths, output lengths
and gaps are stratified quantiles of the traffic file's distributions,
each shuffled under ``trace_seed``; the gaps are scaled so that one
cycle lasts exactly ``seconds``.  ``--seed`` chooses the token ids and
nothing else: every seed offers the same (prompt, output) pairs at the
same instants.

The issue also asked for the seed to rotate the point of the cycle at
which the replay starts.  Measured (my chip run 5, PR 23, 2 x 6 runs):
two runs of one seed agreed within about 2% on the median latency, runs
of different seeds differed by up to 14% - at 0.8 of the knee the queue
remembers far more than the ten seconds of warm traffic, so each start
point was its own transient and its own experiment.  The replay
therefore always starts at the cycle's first request.
"""

from __future__ import annotations

import numpy as np

from . import _quantiles as q


def cycle(traffic: dict, seconds: float):
    """The trace_seed-fixed cycle: (prompt_len[N], out_len[N], gap[N])
    with gap[i] the wait after request i and sum(gap) == seconds."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    order = np.random.default_rng(traffic["trace_seed"])
    prompts = q.stratified(traffic["prompt_tokens"], n)
    outs = q.stratified(traffic["output_tokens"], n)
    gaps = q.stratified(traffic["gaps"], n)
    prompts = prompts[order.permutation(n)]
    outs = outs[order.permutation(n)]
    gaps = gaps[order.permutation(n)]
    return prompts, outs, gaps * (seconds / gaps.sum())


def schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> dict:
    """What the client replays: warm requests (the tail of the cycle,
    due at negative times), then one whole cycle due in [0, seconds).
    Times are relative to the window's first instant."""
    seed = q.check_seed(seed)
    prompts, outs, gaps = cycle(traffic, seconds)
    n = len(prompts)
    rng = np.random.default_rng([seed, 1])
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    reqs = [{"i": i, "due": float(t), "window": True,
             "prompt": q.token_ids(rng, int(prompts[i]), vocab),
             "max_new": int(outs[i])} for i, t in enumerate(due)]
    # warm traffic from the same trace: walk the cycle backwards from
    # its first request until warm_seconds are covered
    warm, t, j = [], 0.0, 0
    while -t < traffic["warm_seconds"]:
        j = (j - 1) % n
        t -= float(gaps[j])
        warm.append({"i": int(j), "due": t, "window": False,
                     "prompt": q.token_ids(rng, int(prompts[j]), vocab),
                     "max_new": int(outs[j])})
    return {"loop": "open", "requests": warm[::-1] + reqs,
            "offered": {"requests": n,
                        "output_tokens": int(outs.sum()),
                        "prompt_tokens": int(prompts.sum())}}


def shapes(traffic: dict, seconds: float, kv_block: int) -> dict:
    """What set-up must warm: the distinct prompt lengths of the cycle,
    and the distinct numbers of full KV blocks a finished request
    commits to the pool (the tokens whose KV exists: the prompt and all
    but the last of the answer; nothing is shared, so all are new)."""
    prompts, outs, _ = cycle(traffic, seconds)
    counts = (prompts + outs - 1) // kv_block if kv_block else []
    return {"prompt_lens": sorted({int(p) for p in prompts}),
            "max_total": int((prompts + outs).max()),
            "commit_block_counts": sorted({int(c) for c in counts})}
