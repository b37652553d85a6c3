"""What both runners share: children, device facts, the trace window."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class Children:
    """Every process the run starts; all stopped and waited for."""

    def __init__(self):
        self.procs: list[subprocess.Popen] = []
        self.tmp = tempfile.mkdtemp(prefix="edl-bench-")

    def spawn(self, argv: list[str], **kw) -> subprocess.Popen:
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen([sys.executable, "-u", *argv], cwd=ROOT,
                                env=env, **kw)
        self.procs.append(proc)
        return proc

    def coord(self):
        """A coordination server (no JAX in it) and a client to it."""
        from edl_tpu.coord.client import connect_wait
        from edl_tpu.utils.network import find_free_port
        port = find_free_port()
        log = open(os.path.join(self.tmp, "coord.log"), "ab")
        self.spawn(["-m", "edl_tpu.coord.server", "--host", "127.0.0.1",
                    "--port", str(port)], stdout=log, stderr=log)
        log.close()
        ep = f"127.0.0.1:{port}"
        return ep, connect_wait(ep)

    def stop(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(10)
        shutil.rmtree(self.tmp, ignore_errors=True)


class Phases:
    """Where set-up goes: seconds since process start at each step,
    printed on one earlier line."""

    def __init__(self, t_start: float):
        self.t_start, self.marks = t_start, []

    def mark(self, name: str) -> None:
        self.marks.append((name, time.monotonic() - self.t_start))

    def line(self) -> str:
        out, prev = [], 0.0
        for name, t in self.marks:
            out.append(f"{name} {t - prev:.1f}")
            prev = t
        return "[bench] set-up, seconds by phase: " + ", ".join(out)


def device_facts() -> dict:
    import jax
    devs = jax.local_devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class TraceWindow:
    """The profiler around a stretch of the window, with one host span
    (``bench/trace_window``) that marks the stretch on the trace's own
    clock; ``trace_reduce`` clips everything to it."""

    def __init__(self, out_dir: str):
        self.dir = out_dir
        self._span = None
        self.t_start = self.t_stop = None

    def start(self) -> None:
        import jax
        from trace_reduce import WINDOW_SPAN
        # the Python tracer hooks every call on every thread and slows a
        # host-bound server several-fold (serving: device idle 81% with
        # it, my chip run 2, PR 23); JAX's own TraceMe spans and the
        # benchmark's annotations are enough to name the gaps
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.t_start = time.monotonic()

    def stop(self) -> None:
        import jax
        self.t_stop = time.monotonic()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        import trace_reduce
        return trace_reduce.reduce_xplane(trace_reduce.find_xplane(self.dir))
