"""The multi-token attention of a head-row layer over its LIVE PREFIX
(``ops/decode_attention.prefix_chunk_attention``: tiles of the slab up
to the call's last position, one softmax carried across them; every
chunk, bucketed and reuse prefill of the one gated GQA layer) against
its roofline: the greater of its FLOPs over the bf16 peak and its live
rows' bytes over the HBM peak (archs/<arch>.prefix_chunk_flops from the
(query, visible row) pairs of the calls' real tokens;
``prefix_chunk_bytes`` from the rows that were LIVE below each call's
last position, whatever the implementation read), over the device time
of that attention in the traced span, found under either of the two
forms the program can give it:

- today's XLA loop: the capture carries no scope and a loop has no name
  of its own, so the runner finds its ``while`` events by the tuple the
  loop carries and hands their seconds over as
  ``trace["scopes"]["attn/prefix_chunk"]``
  (``runners/serve_deltagqa.loop_seconds``: the union of the events'
  intervals, so a loop and its body count once);
- a kernel that takes the loop's place: its events in ``trace["ops"]``
  under a name that starts with ``prefix_chunk`` (``KERNEL``; what a
  Pallas successor is to be named, as ``long_attend_roofline`` knows
  ``decode_attend``).

Pairs, rows and tokens are COUNTED in the span: the runner reads the
engine's cumulative ``kv_prefill_pairs``, ``kv_prefill_rows_live`` and
``kv_prefill_tokens`` less ``kv_prefill_tokens_skipped`` just inside the
trace's two edges (``trace_span_counters``).  What the program does
beyond that (rows of a tile past the call's end, a last bucket's
padding, masked pairs computed and thrown away) is its cost and is not
in the numerator: at 512 queries against 70k rows the masked pairs are
under 1% of the computed ones.  A program with neither form or without
the counters (the parent commit; a dense path) reports nothing."""
import importlib
import re

KERNEL = re.compile(r"^prefix[-_]chunk", re.I)


def read(ctx):
    tr, conf = ctx["trace"], ctx["conf"]
    span = ctx["counters"].get("trace_span_counters")
    if (not tr or not span or not span.get("kv_prefill_pairs")
            or not span.get("kv_prefill_rows_live")):
        return None
    secs = ((tr.get("scopes") or {}).get("attn/prefix_chunk", 0.0)
            + sum(s for n, s in (tr.get("ops") or {}).items()
                  if KERNEL.search(n)))
    if secs <= 0:
        return None
    arch = importlib.import_module(f"archs.{conf['run']['arch']}")
    if not hasattr(arch, "prefix_chunk_flops"):
        return None
    layers = arch.gqa_layers(conf)
    tokens = (span.get("kv_prefill_tokens", 0)
              - span.get("kv_prefill_tokens_skipped", 0)) * layers
    least = max(
        arch.prefix_chunk_flops(conf, span["kv_prefill_pairs"])
        / ctx["peak"]["bf16_flops_per_s"],
        arch.prefix_chunk_bytes(conf, span["kv_prefill_rows_live"], tokens)
        / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
