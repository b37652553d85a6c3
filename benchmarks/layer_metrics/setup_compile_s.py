"""Seconds of set-up inside XLA for the program's own programs: the
``compile`` stage of the program-build ledger over its ``build/*``
spans, every component but ``other``.  JAX's
``backend_compile_duration``: compiling on a persistent-cache miss,
reading and deserialising on a hit (``setup_cache_hit_share`` says
which the run was)."""
from . import setup_trace_lower_s as ledger


def read(ctx):
    rows = ledger.rows()
    if rows is None:
        return None
    return ledger.seconds(rows, ("compile_s",), ledger.own_builds)
