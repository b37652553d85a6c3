"""Of the (token, expert) pairs the routers routed in the window, the
share that landed on experts this device holds - the pairs its grouped
matmuls computed: stats()'s moe_assignments over
moe_assignments_routed (``top_k`` x sparse layers x tokens), both
differenced.  16 of 128 experts under an even router: 12.5%.  Every
expert metric of the cell is read against it: the pairs computed, the
experts touched and the grouped matmuls' time all scale with it."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("moe_assignments_routed"):
        return None
    return 100.0 * c["moe_assignments"] / c["moe_assignments_routed"]
