"""Tokens a live (slot, pass) pair delivered, the mean over the window:
ContinuousBatcher.stats()'s blockdiff_tokens_delivered (what the commits
handed to requests: blocks x L less the positions prompts gave and the
cut ends) over blockdiff_slot_passes (the pairs the pass programs
counted live), both differenced.  A block of L positions costs its
denoise passes and one commit pass, so L = 4 at 4 denoising steps reads
0.8; a token step of the other cells would read 1.  None where the
engine has no such counters (a causal configuration, the parent
commit)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("blockdiff_slot_passes"):
        return None
    return c.get("blockdiff_tokens_delivered", 0) / c["blockdiff_slot_passes"]
