"""Programs the process asked XLA for up to the run's end, over every
row of the program-build ledger (spans, state set-up and ``other``
alike): how many programs a start pays for, each traced, lowered and
compiled or read back."""
from . import setup_trace_lower_s as ledger


def read(ctx):
    rows = ledger.rows()
    if rows is None:
        return None
    return float(sum(r.get("builds", 0) for r in rows.values()))
