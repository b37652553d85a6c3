"""Seconds of set-up spent tracing and lowering the program's own
programs: the ``trace`` and ``lower`` stages of the program-build
ledger (``edl_tpu.obs.ledger.PROGRAM_BUILDS``) over its ``build/*``
spans, every component but ``other``.  Python turning functions into
jaxprs and jaxprs into MLIR, where a ``pallas_call`` is lowered once a
call site.  The ledger is process-wide and the window builds nothing
(``serve_compiles_in_window``), so its totals at the run's end are
set-up's.

``rows()`` and ``seconds()`` are what the other ``setup_*`` readers
share: the process's ledger by row, None on a program without one."""

STAGES = ("trace_s", "lower_s", "compile_s", "run_s")
UNLABELLED = "other"


def rows():
    """``{(kind, component, family): {field: value}}`` of the process's
    program-build ledger, or None where the program has none (the
    parent commit) or it has booked nothing."""
    from edl_tpu.obs import ledger
    builds = getattr(ledger, "PROGRAM_BUILDS", None)
    if builds is None:
        return None
    out = {}
    for key, value in builds.totals().items():
        kind, component, rest = key.split("/", 2)
        family, field = rest.rsplit("/", 1)
        out.setdefault((kind, component, family), {})[field] = value
    return out or None


def seconds(rows_, fields, keep):
    """Sum of ``fields`` over the rows ``keep(kind, component)`` says."""
    return float(sum(row.get(f, 0.0) for (kind, component, _), row
                     in rows_.items() if keep(kind, component)
                     for f in fields))


def own_builds(kind, component):
    return kind == "build" and component != UNLABELLED


def read(ctx):
    got = rows()
    if got is None:
        return None
    return seconds(got, ("trace_s", "lower_s"), own_builds)
