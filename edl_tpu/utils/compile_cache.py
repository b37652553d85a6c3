"""One place that decides where XLA's persistent compile cache lives.

Every launcher-restarted trainer and every replica start would
otherwise recompile from nothing (the unrolled 12-layer train step is
the long pole of a stop-resume).  The cache directory is part of
nothing the program computes, but it must be the SAME path in every
process and every run, or nothing ever hits.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Call before the first compile of any entry point that compiles
    for the device.  ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it
    itself and nothing is touched.  Unset: ``<checkout>/.jax_cache``,
    the same for every process of every run.  Returns the directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
