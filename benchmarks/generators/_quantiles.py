"""Stratified quantiles: the N values a distribution 'should' give.

Drawing N values at run time makes every seed a different experiment
(PR 22 was refused for that).  Taking the (i + 0.5)/N quantiles gives
the same multiset every time; a shuffle under the traffic file's own
``trace_seed`` orders them.  numpy only: the load generator's process
never imports JAX.
"""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def stratified(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of ``dist`` as integers (token
    counts) or floats (exponential gaps of mean 1)."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
        return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)
    if kind == "uniform_quantiles":
        x = dist["min"] + u * (dist["max"] - dist["min"])
        return np.rint(x).astype(np.int64)
    if kind == "exponential":
        return -np.log1p(-u)          # mean -> 1 as n grows
    if kind == "mixture":
        return np.asarray(mixture(dist["parts"], n), np.int64)
    if kind == "listed":
        # any other distribution is a data file: its n quantiles, listed
        x = np.asarray(dist["values"])
        if len(x) != n:
            raise ValueError(f"{len(x)} listed values for {n} requests")
        return x
    raise ValueError(f"unknown distribution {kind!r}")


def mixture(parts: list[dict], n: int) -> list[int]:
    """The n stratified quantiles of a mixture whose components do not
    overlap (so each component's own quantiles, in the order given, are
    the mixture's): round(share x n) of every component but the last,
    which takes the rest.  ``{"dist": "mixture", "parts": [...]}`` in
    a traffic file is this list at N = rate x seconds, so a rate sweep
    changes the rate and nothing else."""
    counts = [int(round(p["share"] * n)) for p in parts[:-1]]
    counts.append(n - sum(counts))
    return [int(v) for p, k in zip(parts, counts) for v in stratified(p, k)]


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> list[int]:
    """n ids in [1, vocab): 0 is left free as a pad id."""
    return rng.integers(1, vocab, n, dtype=np.int64).tolist()


def check_seed(seed: int) -> int:
    if not 0 <= seed < (1 << 63):
        raise ValueError(f"--seed {seed} out of range")
    return int(seed)
