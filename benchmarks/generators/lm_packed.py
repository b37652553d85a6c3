"""Packed LM batches from a seeded order-1 Markov chain.

A vectorised copy of ``examples/lm/train_lm.py:markov_corpus`` (listed
in PERF.md for a later PR to point at one of the two): each token has
``successors`` likely successors, followed with probability ``peak``,
so there is sequence structure for the loss to fall on.  The chain's
table comes from the traffic file's ``trace_seed``; ``--seed`` draws
the sequences.  Every batch is full: every seed does the same work.
"""

from __future__ import annotations

import numpy as np


def batches(traffic: dict, seed: int, batch: int, seq_len: int, vocab: int):
    """Yield ``{"ids": int32[batch, seq_len + 1]}`` for ever."""
    k, peak = traffic["chain"]["successors"], traffic["chain"]["peak"]
    nxt = np.random.default_rng(traffic["trace_seed"]).integers(
        0, vocab, (vocab, k))
    rng = np.random.default_rng([int(seed), 4])
    rows = np.arange(batch)
    while True:
        follow = rng.random((batch, seq_len + 1)) < peak
        pick = rng.integers(0, k, (batch, seq_len + 1))
        jump = rng.integers(0, vocab, (batch, seq_len + 1))
        ids = np.empty((batch, seq_len + 1), np.int32)
        t = jump[:, 0]
        for i in range(seq_len + 1):
            ids[:, i] = t
            t = np.where(follow[:, i], nxt[t, pick[rows, i]], jump[:, i])
        yield {"ids": ids}
