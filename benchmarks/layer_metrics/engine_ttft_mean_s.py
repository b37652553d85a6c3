"""Mean time from submit() to the request's first token on the host,
as the engine sees it (the client still receives whole answers):
ttft_s_sum over first_tokens, both differenced over the window."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("first_tokens") or "ttft_s_sum" not in c:
        return None
    return c["ttft_s_sum"] / c["first_tokens"]
