"""Of the slot states the step programs' state-space layers read and
wrote in the window, the share that belonged to live slots:
ContinuousBatcher.stats()'s ssm_state_steps over ssm_state_steps_run,
both differenced.  The denominator is the step programs' own count:
each state-space layer's one-token update adds the slot states its
call fetches and writes back, on the chip read off the fetch plan the
``ssm_step`` kernel runs under (``ops/ssm.slots_fetched``), every slot
on the einsum path.  100 while free slots cost nothing; a plan that
fetched free slots' state would read live slots over all slots.  What
``decode_kv_live_share`` is to the KV slabs."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("ssm_state_steps_run"):
        return None
    return 100.0 * c["ssm_state_steps"] / c["ssm_state_steps_run"]
