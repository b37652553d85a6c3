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
    """Open loop: each answered request is credited its output tokens
    times the share of its life, send to last byte, that lies inside
    [t0, t0 + seconds); the sum per second of the window.  A request
    that failed or never came back is credited nothing.

    While the system keeps up this is the offered rate (what leaves the
    window by one edge enters by the other: the trace is cyclic and
    warm traffic precedes it); under a growing backlog every life
    lengthens, each request's share of the window shrinks and the value
    falls.  Until PR 40 an answer's tokens were credited whole at the
    instant of its last byte, so one long answer that ended 0.1 s
    before or after an edge moved the value by all its tokens (12.8%
    in one cell); now such a move is worth its tokens times 0.1 s over
    its life.

    A model, not a count of delivered tokens: an answer's tokens are
    spread evenly over its life, queue and prefill included.  A stall
    moves it by the work it pushes across an edge, in either direction
    (a host that hung 3 s in the warm traffic read 7.5% ABOVE the
    offered rate: PERF.md section 2)."""
    t1 = t0 + seconds
    got = 0.0
    for r in records:
        if not r["ok"] or r.get("t_done") is None:
            continue
        inside = min(r["t_done"], t1) - max(r["t_send"], t0)
        if inside > 0:          # and so is its life, which holds it
            got += r["n_got"] * inside / (r["t_done"] - r["t_send"])
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
