"""Mean gap between a request's tokens after its first, over the
requests finished in the window: decode_s_sum (done - first token)
over decode_tokens (tokens - 1), both differenced."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("decode_tokens") or "decode_s_sum" not in c:
        return None
    return 1e3 * c["decode_s_sum"] / c["decode_tokens"]
