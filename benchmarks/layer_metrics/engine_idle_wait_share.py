"""Share of the window the engine thread spent blocked with nothing to
do (engine/idle_wait: no live slot, no chunk in flight, an empty
queue): idle_wait_s differenced, over the window.  A reading, not a
goal: under the knee of an open loop it is the headroom, and it is
what separates "waiting for requests" from "host Python" in the
device's idle share."""


def read(ctx):
    c = ctx["counters"]
    if "idle_wait_s" not in c or not c.get("window_s"):
        return None
    return 100.0 * c["idle_wait_s"] / c["window_s"]
