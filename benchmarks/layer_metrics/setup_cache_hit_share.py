"""Persistent compile-cache hits over the compile requests that used
the cache, every label of the program-build ledger: 100 is a warm run,
anything under it names a run (a side of a comparison) that compiled.
None when no request used the cache."""
from . import setup_trace_lower_s as ledger


def read(ctx):
    rows = ledger.rows()
    if rows is None:
        return None
    hits = sum(r.get("cache_hits", 0) for r in rows.values())
    asked = hits + sum(r.get("cache_misses", 0) for r in rows.values())
    return 100.0 * hits / asked if asked else None
