"""Seconds the engine thread spent building programs inside the window:
``program_build_s`` of ``ContinuousBatcher.stats()`` (the program-build
ledger's sum for that thread, spans and unlabelled compiles alike),
differenced at the window's edges.  The compile layer read from inside
the program; 0 wherever ``serve_compiles_in_window`` is 0."""


def read(ctx):
    return ctx["counters"].get("program_build_s")
