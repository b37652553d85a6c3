"""Ring positions the window layers' decode reads FETCHED over the
positions their windows held, the mean over the window:
ContinuousBatcher.stats()'s decode_kv_tokens_window_read over
decode_kv_tokens_window_need, both differenced (per token step and live
slot of one window layer: whole attend blocks of the slot's ring on the
chip; ``min(length, window)``).  1.0 reads the window and no more; a
read of a ``max_len`` slab is ``max_len / window`` (128 at 16k).  The
ring is the window and what a pool commit reads back, rounded up to
lanes (256 positions for a window of 128), and one attend block."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("decode_kv_tokens_window_need"):
        return None
    return (c["decode_kv_tokens_window_read"]
            / c["decode_kv_tokens_window_need"])
