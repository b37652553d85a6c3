"""Rows the latent layers' multi-token calls READ over the rows their
masks let through, the mean over the window:
ContinuousBatcher.stats()'s latent_prefill_rows_read over
latent_prefill_rows_live, both differenced (per multi-token call, lane
and latent layer).  The engine counts both on the host from the call's
offset and length: the rows up to the call's last position, and the
whole tiles the expanded path's loop fetches to cover them under the
tile it runs under (``ops/latent_attention.expand_block``).  1.0 reads
what a chunk can see and no more; a program that attends the whole slab
under its mask reads ``max_len`` a call (PR 37's read 9.3 in this cell;
PR 38's tiles 1.13).  None where the program has no such counters (PR
37's commit and before) or ran no multi-token call on a latent layer."""


def read(ctx):
    c = ctx["counters"]
    if (not c.get("latent_prefill_rows_live")
            or not c.get("latent_prefill_rows_read")):
        return None
    return c["latent_prefill_rows_read"] / c["latent_prefill_rows_live"]
