"""Closed-loop multi-turn sessions over one long document each.

The trace is ``sessions_in_trace`` sessions fixed by ``trace_seed``:
document lengths and question lengths are stratified quantiles,
shuffled.  A session is its document followed by
``questions_per_session`` questions; every turn carries the whole
history (document, earlier questions, earlier answers) and asks for
``output_tokens``.  ``clients`` clients each walk the cyclic list of
sessions from their own start point, the next turn sent when the
previous answer is in.  ``--seed`` chooses the token ids and rotates
the start points: every seed runs sessions of the same lengths.
"""

from __future__ import annotations

import numpy as np

from . import _quantiles as q


def sessions(traffic: dict):
    """(doc_len[S], question_len[S, Q]) fixed by trace_seed."""
    s, nq = traffic["sessions_in_trace"], traffic["questions_per_session"]
    order = np.random.default_rng(traffic["trace_seed"])
    docs = q.stratified(traffic["document_tokens"], s)[order.permutation(s)]
    qs = q.stratified(traffic["question_tokens"], s * nq)
    return docs, qs[order.permutation(s * nq)].reshape(s, nq)


def schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> dict:
    seed = q.check_seed(seed)
    docs, qs = sessions(traffic)
    s, nq = qs.shape
    rng = np.random.default_rng([seed, 2])
    rot = int(rng.integers(s))
    c = traffic["clients"]
    plans = []
    for k in range(c):
        # clients spread evenly over the cycle, and staggered over the
        # turns of a session: client k enters its first session at turn
        # k % nq, so the window opens on a mix of first and later turns
        first = (rot + k * s // c) % s
        plans.append({"client": k, "first_session": first,
                      "first_turn": k % nq})
    # ids for every session of the cycle, drawn once: a client that
    # wraps replays a session's lengths with fresh ids (ids are keyed
    # by (session, lap) at the client)
    return {"loop": "closed", "clients": plans,
            "doc_lens": docs.tolist(), "question_lens": qs.tolist(),
            "output_tokens": int(traffic["output_tokens"]),
            "think_seconds": float(traffic["think_seconds"]),
            "warm_turns": int(traffic["warm_turns"]),
            "vocab": int(vocab), "id_seed": [seed, 3]}


def session_ids(plan: dict, session: int, lap: int, client: int):
    """(document ids, [question ids]) of one session as one client runs
    it on one lap: two clients that reach the same session of the cycle
    send documents of the same length and different ids, so that no
    client ever finds another's document in the pool."""
    rng = np.random.default_rng(plan["id_seed"] + [session, lap, client])
    doc = q.token_ids(rng, plan["doc_lens"][session], plan["vocab"])
    qs = [q.token_ids(rng, n, plan["vocab"])
          for n in plan["question_lens"][session]]
    return doc, qs


def shapes(traffic: dict, seconds: float, kv_block: int) -> dict:
    """What set-up must warm: document lengths, the longest question,
    and the distinct numbers of NEW full KV blocks a finished turn
    commits (a turn's KV covers its prompt and all but the last token of
    its answer; the blocks of the turns before are in the pool)."""
    docs, qs = sessions(traffic)
    nq, out = qs.shape[1], traffic["output_tokens"]
    longest = int(docs.max() + qs.sum(axis=1).max() + nq * out)
    counts = set()
    for d, row in zip(docs, qs):
        prompt, have = int(d), 0
        for q in row:
            prompt += int(q)
            full = (prompt + out - 1) // kv_block if kv_block else 0
            counts.add(full - have)
            have, prompt = full, prompt + out
    return {"doc_lens": sorted({int(d) for d in docs}),
            "question_max": int(qs.max()), "max_total": longest,
            "commit_block_counts": sorted(counts - {0})}
