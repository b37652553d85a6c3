"""Seconds of set-up spent RUNNING what was built: the ``run`` stage of
the program-build ledger's ``build/*`` spans (a span's wall seconds
less JAX's trace, lower and compile inside it): ``warm()`` executing
each prefill program once, the first train step, a pool commit's first
dispatch, each waited for."""
from . import setup_trace_lower_s as ledger


def read(ctx):
    rows = ledger.rows()
    if rows is None:
        return None
    return ledger.seconds(rows, ("run_s",), ledger.own_builds)
