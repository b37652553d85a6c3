"""Seconds in the program-build ledger's ``setup/<component>/state``
spans, all four stages: weights cast and placed, the slot cache and the
block pool allocated (``ContinuousBatcher.__init__``), the trainer's
state built or restored, and the one-off programs that takes."""
from . import setup_trace_lower_s as ledger


def read(ctx):
    rows = ledger.rows()
    if rows is None:
        return None
    return ledger.seconds(rows, ledger.STAGES,
                          lambda kind, component: kind == "setup")
