"""Of the (real token, layer) visits a full-depth prefill of the
window's prompt tokens would have made, the share the last-position cut
left out: ContinuousBatcher.stats()'s ``prefill_layer_visits_cut`` over
``prefill_layer_visits`` + ``prefill_layer_visits_cut``, both
differenced.  The layers of the stack's tail keep nothing a position, so
a program that samples runs them at one row a lane and a chunk that
samples nothing leaves them out: tail layers over all layers (14 / 32 =
43.75) less the sampling rows.  0, or no counter, is a stack without a
tail, or a cut that was lost."""


def read(ctx):
    c = ctx["counters"]
    ran, cut = c.get("prefill_layer_visits"), c.get("prefill_layer_visits_cut")
    if not ran or cut is None:
        return None
    return 100.0 * cut / (ran + cut)
