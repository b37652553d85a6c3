"""Seconds of tracing, lowering and compiling that no span of the
program-build ledger owns (component ``other``): eager one-op
programs, the runner's own weights, probes and reference.  The
ledger's blind spot, as ``serve_idle_unattributed_share`` is the idle
gaps'."""
from . import setup_trace_lower_s as ledger


def read(ctx):
    rows = ledger.rows()
    if rows is None:
        return None
    return ledger.seconds(
        rows, ledger.STAGES,
        lambda kind, component: component == ledger.UNLABELLED)
