"""What a later turn's re-attach costs on the device, the mean over the
traced span in milliseconds: the device time of the ``load`` program
(``ContinuousBatcher._load_prefix_fn``: a fresh one-lane slab with the
chain's 500-1,800 blocks of the gated GQA layer gathered to the front
of its K and V rows and one 13 MB delta-rule state snapshot restored,
its index at the prefix's end), the profiler's module line a run.  The
host's side is the span ``engine/reattach`` (blocks, bytes, whether a
snapshot was restored) in any capture; the tail's re-prefill and the
insert into the slot are other programs.  A stack that fuses the gather
into its reuse prefill (no state class) has no ``load`` program and
reports nothing."""
import re

PROGRAM = re.compile(r"^jit_load\b")


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    runs = [d for n, m in tr["modules"].items() if PROGRAM.search(n)
            for d in m["durations_s"]]
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
