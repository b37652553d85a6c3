"""Expert weight sets the decode step's expert kernel fetched over the
experts its batches touched: ContinuousBatcher.stats()'s
moe_decode_experts_fetched over moe_decode_experts_touched, both
differenced over the window.  The numerator is the step programs' own
count: each expert layer's one-token call adds the weight sets its
``moe_decode_gmm`` kernel fetched, which the kernel counts as it starts
each chunk's copy from HBM (``ops/moe.decode_gmm``).  1.0 = every
touched expert's matrices read once and no other expert's; a kernel
that fetched untouched experts, or one expert twice, would read above
it.  None where the program has no such counter (the parent commit) or
counted nothing (``ragged_dot`` ran, whose reads are not the program's
to count)."""


def read(ctx):
    c = ctx["counters"]
    if (not c.get("moe_decode_experts_fetched")
            or not c.get("moe_decode_experts_touched")):
        return None
    return c["moe_decode_experts_fetched"] / c["moe_decode_experts_touched"]
