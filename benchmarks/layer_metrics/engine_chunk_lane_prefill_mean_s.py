"""Mean time one long prompt holds the chunk lane: from its admission
(its first chunk's tick) to its first token read on the host, over the
requests admitted down the chunk lane whose first token came in the
window: stage_prefill_chunk_sum_s over stage_prefill_chunk_n,
differenced.  One chunk a tick, one prompt at a time: this times the
long prompts' arrival rate is the lane's occupancy.  None where the
program has no such counters or no such request."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("stage_prefill_chunk_n"):
        return None
    return c["stage_prefill_chunk_sum_s"] / c["stage_prefill_chunk_n"]
