"""Share of the engine's ticks that enqueued their programs (a decode
step, an insert) while the tick before them was still unread:
ContinuousBatcher.stats()'s lookahead_ticks over ticks, both differenced
over the window.  Inside a busy period every tick with a live slot or an
admission is one but the first (nothing to look past), so the device
holds its next program while the host reads, books and admits; a tick
that only advances a chunk of a long prompt reads nothing, is not
counted, and neither is the tick after it (a cell whose long prompts
prefill with no slot live reads that share lower).  An engine that reads
every tick before it enqueues the next counts none.  None where the
program has no such counter."""


def read(ctx):
    c = ctx["counters"]
    if "lookahead_ticks" not in c or not c.get("ticks"):
        return None
    return 100.0 * c["lookahead_ticks"] / c["ticks"]
