"""90th percentile of the engine-side time to first token (submit()
to the request's first token read on the host: queue wait + prefill,
for a long prompt all its chunks) over the requests whose first token
came in the window: the request-stage ledger's ``stage_ttft_le_<edge>``
bucket counts, differenced and read as engine_queue_wait_p90_s reads
its own.  engine_ttft_mean_s is the same stamps' mean.  None where the
program has no such counters."""
from layer_metrics.engine_queue_wait_p90_s import ladder_quantile


def read(ctx):
    return ladder_quantile(ctx["counters"], "ttft", 0.9)
