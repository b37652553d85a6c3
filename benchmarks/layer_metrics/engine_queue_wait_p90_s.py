"""90th percentile of the time from submit() to admission over the
requests admitted in the window, out of the engine's request-stage
ledger: ContinuousBatcher.stats()'s cumulative bucket counts
``stage_queue_wait_le_<edge>`` (one geometric ladder, ratio 1.5 from
2 ms to 50 s), differenced over the window like every other counter.
The rank's bucket, log-linear inside it; the first bucket starts one
ratio under its edge, and a rank beyond the last finite edge reads that
edge (a lower bound).  engine_queue_wait_mean_s is the same stamps'
mean.  None where the program has no such counters (the parent commit)
or admitted nobody."""
import math


def ladder_quantile(counters, stage, q):
    head = f"stage_{stage}_le_"
    ladder = sorted((math.inf if k[len(head):] == "inf"
                     else float(k[len(head):]), v)
                    for k, v in counters.items() if k.startswith(head))
    if len(ladder) < 3 or ladder[-1][1] <= 0:
        return None
    rank = q * ladder[-1][1]
    below, lower = 0, ladder[0][0] ** 2 / ladder[1][0]
    for edge, acc in ladder:
        if acc >= rank and acc > below:
            if edge == math.inf:
                return lower
            return lower * (edge / lower) ** ((rank - below) / (acc - below))
        below, lower = acc, edge
    return lower


def read(ctx):
    return ladder_quantile(ctx["counters"], "queue_wait", 0.9)
