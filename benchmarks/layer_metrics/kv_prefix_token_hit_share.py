"""Prompt tokens the paged cache spared the prefill
(kv_prefill_tokens_skipped, differenced) over the prompt tokens
submitted in the window."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("prompt_tokens_submitted"):
        return None
    return (100.0 * c.get("kv_prefill_tokens_skipped", 0)
            / c["prompt_tokens_submitted"])
