"""The share of live (slot, pass) pairs that were COMMIT passes (a
finished block through the model once more, so that its rows hold the
final tokens' keys and values): ContinuousBatcher.stats()'s
blockdiff_blocks_committed over blockdiff_slot_passes, both differenced.
20% at L = 4 and 4 denoising steps: what a commit fused with the next
block's first pass would take away.  None where the engine has no such
counters."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("blockdiff_slot_passes"):
        return None
    return (100.0 * c.get("blockdiff_blocks_committed", 0)
            / c["blockdiff_slot_passes"])
