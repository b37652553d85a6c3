"""Of the positions the prefill, chunk and reuse programs ran through
the state-space layers' scan in the window, the share that was padding
(bucket padding, masked so that it moves no state):
ContinuousBatcher.stats()'s ssm_prefill_positions_pad over
ssm_prefill_positions, both differenced.  What the bucket ladder costs
a mixer that cannot skip a masked position for free."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("ssm_prefill_positions"):
        return None
    return 100.0 * c["ssm_prefill_positions_pad"] / c["ssm_prefill_positions"]
