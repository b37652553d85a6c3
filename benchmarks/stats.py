"""The benchmark's own arithmetic on request records (no JAX)."""

from __future__ import annotations

import math


def percentile(values: list[float], p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least p% of
    the sample at or below it.  No interpolation, so a tail is a
    latency some request really had."""
    if not values:
        raise ValueError("no values")
    xs = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[rank - 1]


def window_token_rate(records: list[dict], t0: float, seconds: float) -> float:
    """Open loop: output tokens of the answers that arrived at the
    client inside [t0, t0 + seconds), per second of the window."""
    got = sum(r["n_got"] for r in records
              if r.get("t_done") is not None and r["ok"]
              and t0 <= r["t_done"] < t0 + seconds)
    return got / seconds


def whole_request_rate(records: list[dict], t0: float, seconds: float) -> float:
    """Closed loop: for each client, the tokens of the turns it started
    AND finished inside the window, over the time from the first such
    start to the last such finish; the sum over clients.  A turn cut by
    an edge of the window counts on neither side, so the edges cost
    nothing but a shorter span."""
    t1 = t0 + seconds
    by_client: dict = {}
    for r in records:
        if r["ok"] and r["t_send"] >= t0 and r["t_done"] <= t1:
            by_client.setdefault(r["client"], []).append(r)
    rate = 0.0
    for rs in by_client.values():
        span = max(r["t_done"] for r in rs) - min(r["t_send"] for r in rs)
        if span > 0:
            rate += sum(r["n_got"] for r in rs) / span
    return rate


def open_latencies(records: list[dict]) -> list[float]:
    """Latency of each answered window request, from the instant it was
    DUE (not sent) to the last byte at the client."""
    return [r["t_done"] - r["t_due"] for r in records
            if r["window"] and r["ok"]]
