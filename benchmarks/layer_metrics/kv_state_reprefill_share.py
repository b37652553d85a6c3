"""Pooled tokens the window prefilled AGAIN because no state snapshot
lay as deep as the blocks, as a share of the prompt tokens the window
prefilled: ContinuousBatcher.stats()'s kv_state_reprefill_tokens over
kv_prefill_tokens less kv_prefill_tokens_skipped, both differenced, in
percent.  A recurrence keeps no snapshot at an answer's end (it can be
saved only where a prefill program is AT), so every later turn of a
session re-prefills the previous answer and what lay past the previous
prompt's last block edge: what ROADMAP R5a (an answer-end snapshot)
would save here.  None where nothing was prefilled or the program has
no such counter."""


def read(ctx):
    c = ctx["counters"]
    done = c.get("kv_prefill_tokens", 0) - c.get("kv_prefill_tokens_skipped",
                                                 0)
    if done <= 0 or "kv_state_reprefill_tokens" not in c:
        return None
    return 100.0 * c["kv_state_reprefill_tokens"] / done
