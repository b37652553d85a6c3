"""Share of the window's queue wait that the CHUNK LANE caused:
ContinuousBatcher.stats()'s queue_wait_cause_lane_s over the sum of the
four causes (slots, lane, group, tick), differenced.  At the end of
every tick's admission the engine charges each request still pending to
why the queue's head stayed: ``lane`` when a slot was free but a long
prompt's chunked admission held the lane, which takes the tick's one
cold group and bars the next long prompt.  It bounds what a cold group
beside a chunk, or a second lane, can give back.  None where the
program has no such counters or nobody waited."""

CAUSES = ("slots", "lane", "group", "tick")


def cause_share(counters, cause):
    keys = [f"queue_wait_cause_{c}_s" for c in CAUSES]
    if any(k not in counters for k in keys):
        return None
    total = sum(counters[k] for k in keys)
    if total <= 0:
        return None
    return 100.0 * counters[f"queue_wait_cause_{cause}_s"] / total


def read(ctx):
    return cause_share(ctx["counters"], "lane")
