"""Positions the latent layers' decode reads FETCHED over the positions
their live slots held, the mean over the window:
ContinuousBatcher.stats()'s latent_tokens_read over latent_tokens_live,
both differenced (per token step, live slot and latent layer).  The
numerator is the step programs' own count: each latent layer's one-token
call adds the positions its ``latent_attend`` kernel fetches, read off
the fetch plan it runs under (``ops/latent_attention.tokens_fetched``:
whole tiles of 512 positions up to each live slot's length, nothing for
a free slot); every slot's whole slab on the einsum path.  1.0 reads
what is live and no more; the einsum path would read ``max_len`` a slot
(32768).  None where the program has no such counter (the parent
commit)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("latent_tokens_live") or not c.get("latent_tokens_read"):
        return None
    return c["latent_tokens_read"] / c["latent_tokens_live"]
