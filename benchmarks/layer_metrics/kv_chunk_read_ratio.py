"""Rows the head-row layers' multi-token calls READ over the rows live
below their last position, the mean over the window:
ContinuousBatcher.stats()'s kv_prefill_rows_read over
kv_prefill_rows_live, both differenced (per multi-token call, lane and
head-row layer).  The engine counts both on the host from the call's
offset and length and the rule the model dispatches by
(``ops/decode_attention.prefix_tiled`` / ``prefix_block``): whole tiles
up to the call's last position on the tiled path, the slab on the dense
one.  1.0 reads what a chunk can see and no more; a program that
attends the whole slab under its mask reads ``max_len`` a call
(``max_len / live``).  None where the program has no such counters (the
parent commit) or ran no multi-token call on a head-row layer."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("kv_prefill_rows_live") or not c.get("kv_prefill_rows_read"):
        return None
    return c["kv_prefill_rows_read"] / c["kv_prefill_rows_live"]
