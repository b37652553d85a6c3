"""Share of the window's queue wait spent with NO FREE SLOT:
queue_wait_cause_slots_s over the sum of the four causes, differenced
(engine_queue_wait_lane_share says how they are charged).  Judged
first: with no slot free no policy of lanes would have admitted the
queue's head.  What is neither this nor the lane's is ``group`` (the
tick's one cold group took another bucket or its cap) and ``tick`` (the
arrival waited for the loop's next admission).  None where the program
has no such counters or nobody waited."""
from layer_metrics.engine_queue_wait_lane_share import cause_share


def read(ctx):
    return cause_share(ctx["counters"], "slots")
