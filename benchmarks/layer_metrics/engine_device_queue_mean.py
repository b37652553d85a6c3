"""Mean depth of the device's queue as the host knows it, seen by each
program as it is enqueued: device_queue_programs_sum over
device_enqueues, differenced.  The engine counts the step, insert,
prefill, chunk and reuse programs it enqueues, and a read of a tick's
results proves every program up to that tick's step has run; the depth
a new program finds is the enqueued less the proven.  1-2 under the
one-tick lookahead; a long prompt prefilling with no slot live enqueues
chunk after chunk and reads nothing, and an arrival's prefill queues
behind them all.  None where the program has no such counters."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("device_enqueues") or "device_queue_programs_sum" not in c:
        return None
    return c["device_queue_programs_sum"] / c["device_enqueues"]
