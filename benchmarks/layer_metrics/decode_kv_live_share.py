"""The share of the KV slabs a decode token step has to read, the mean
over the window: ContinuousBatcher.stats()'s decode_kv_tokens_live (per
token step of the decode program, the positions its live slots hold:
prompt + emitted) over decode_kv_tokens_slab (slots x max_len per token
step: what a read of the whole slabs touches), both differenced.  What
decode attention saves by stopping at each slot's length, and skipping
free slots, is 100 minus this."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("decode_kv_tokens_slab"):
        return None
    return 100.0 * c["decode_kv_tokens_live"] / c["decode_kv_tokens_slab"]
