"""Mean time from submit() to admission of the requests admitted down
the COLD lane in the window (a prompt of at most prefill_chunk tokens
with no prefix in the pool: a same-bucket group prefilled in one
program): stage_queue_wait_cold_sum_s over stage_queue_wait_cold_n,
differenced.  What a SHORT prompt waits, where engine_queue_wait_mean_s
averages it with the long prompts queueing for the lane.  None where
the program has no such counters or no such admission."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("stage_queue_wait_cold_n"):
        return None
    return c["stage_queue_wait_cold_sum_s"] / c["stage_queue_wait_cold_n"]
