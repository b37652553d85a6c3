"""Median over the window's requests of: latency at the client (from
the instant the request was due) minus the engine's own submit->done
for the same request (joined by the prompt's hash): what the gateway,
the replica's RPC surface and the wire add."""
import stats


def read(ctx):
    xs = ctx["counters"].get("gateway_overheads_s")
    return stats.percentile(xs, 50) if xs else None
