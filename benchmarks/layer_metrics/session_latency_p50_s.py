"""Median turn latency at the client, turns started and finished in the
window."""
import stats


def read(ctx):
    xs = [r["t_done"] - r["t_send"] for r in ctx["records"] if r["ok"]]
    return stats.percentile(xs, 50) if xs else None
