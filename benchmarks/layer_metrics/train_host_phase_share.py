"""Share of the trainer loop's step time the host spent obtaining the
batch and in the framework's per-step hooks (heartbeat, preempt check,
delta staging): the step ledger's data_wait + hooks over the wall time
between completed steps, differenced over the window, less the time the
benchmark's generator itself waited for the device (it keeps the host
two steps ahead, and the ledger books that wait as data_wait).  Host
clock; the host's work overlaps the device's, so a small share costs
nothing and a share near 100 means the host is the bottleneck."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("step_s"):
        return None
    host = c["phase_data_wait_s"] + c["phase_hooks_s"] - c.get("paced_s", 0.0)
    return 100.0 * max(0.0, host) / c["step_s"]
