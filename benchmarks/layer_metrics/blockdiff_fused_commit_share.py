"""The share of commits that rode the next block's first pass (one
forward that writes the finished block's rows AND opens the slot's next
block, in place of a pass of its own that streams every weight for L
rows whose logits nobody reads): ContinuousBatcher.stats()'s
blockdiff_commits_fused over blockdiff_blocks_committed, both
differenced.  All but each answer's last commit can: 97-99% at answers
of 32-128 blocks.  None where the engine lacks the counter (a causal
configuration, a commit before PR 49)."""


def read(ctx):
    c = ctx["counters"]
    if ("blockdiff_commits_fused" not in c
            or not c.get("blockdiff_blocks_committed")):
        return None
    return (100.0 * c["blockdiff_commits_fused"]
            / c["blockdiff_blocks_committed"])
