"""One place that decides where XLA's persistent compile cache lives.

Every launcher-restarted trainer and every replica start would
otherwise recompile from nothing (the unrolled 12-layer train step is
the long pole of a stop-resume).  The cache directory is part of
nothing the program computes, but it must be the SAME path in every
process and every run, or nothing ever hits.

It is also the one place every compiling entry point passes before its
first compile, so it is where JAX's own clock of a program's build is
handed to the program-build ledger (``obs/ledger.py``): where a cold
start or a resize went is then in ``/metrics``, in the resize record
and in ``ContinuousBatcher.stats()``.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_listening = False


def _listen_to_builds() -> None:
    """``jax.monitoring``'s compile events into the program-build
    ledger, once a process.  A listener runs on the compiling thread
    and only when something is traced, lowered or compiled."""
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring

    from edl_tpu.obs.ledger import PROGRAM_BUILDS
    monitoring.register_event_duration_secs_listener(
        PROGRAM_BUILDS.on_duration)
    monitoring.register_event_listener(PROGRAM_BUILDS.on_event)


def enable_compile_cache() -> str:
    """Call before the first compile of any entry point that compiles
    for the device.  ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it
    itself and nothing is touched.  Unset: ``<checkout>/.jax_cache``,
    the same for every process of every run.  Returns the directory.
    Either way the program-build ledger starts listening."""
    _listen_to_builds()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
