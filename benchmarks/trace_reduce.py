"""From the profiler's xplane to what the per-layer readers need.

One reduction for every cell and every later PR: device busy/idle by
the union of op intervals, per-op time under stable names, executed
programs, idle gaps attributed to what the host was doing, collectives
and the part of them no compute covers.  Reads the file with
``jax.profiler.ProfileData`` and nothing else.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench/trace_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|allreduce|allgather|reducescatter", re.I)
# host events that only say "a thread exists" or wrap the whole window
_HOST_NOISE = re.compile(r"^(\$.*(start_trace|stop_trace|__exit__|__enter__)"
                         r"|ThreadpoolListener|PythonRefManager"
                         r"|bench/trace_window)")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no xplane under {trace_dir}")
    return found[-1]


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?")


def op_name(name: str) -> str:
    m = _HLO.match(name)
    return m.group(1) if m else name


def stable_name(name: str) -> str:
    """An op's name with its (first) result shape, in the characters a
    metric name may have.  The trace names a device op by its HLO text,
    ``%fusion.617 = f32[12]{0} fusion(...)``: that gives
    ``fusion.617_f32_12_``.  The same op keeps its name across runs,
    and two fusions with one number but different shapes stay apart."""
    m = _HLO.match(name)
    text = name if not m else f"{m.group(1)}_{m.group(2) or ''}"
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", text)[:64]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Parts of the (unioned) intervals ``a`` not covered by ``b``."""
    out, b = [], union(b)
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.duration_ns), ev


def reduce_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
        elif plane.name.startswith("/host:CPU"):
            host.append(plane)
    if not devices:
        raise RuntimeError(
            f"no /device:TPU plane in {path}: planes "
            f"{[p.name for p in data.planes]}")
    # host side: every event of every thread, as (name, start, end)
    host_events, window = [], None
    for plane in host:
        for line in plane.lines:
            for name, s, d, _ in _events(line):
                if name == WINDOW_SPAN:
                    window = (s, s + d)
                if d > 0 and not _HOST_NOISE.match(name):
                    host_events.append((name, s, s + d, line.name))
    per_device, ops, modules, gaps = {}, {}, {}, {}
    index = _HostIndex(host_events)
    coll_total = coll_exposed = 0.0
    lo = hi = None
    for dev, plane in sorted(devices.items()):
        op_iv, coll_iv, comp_iv = [], [], []
        lines = {ln.name: ln for ln in plane.lines}
        op_line = lines.get(OPS_LINE)
        if op_line is None:
            raise RuntimeError(f"device plane {plane.name} has no "
                               f"{OPS_LINE!r} line: {sorted(lines)}")
        evs = []
        for name, s, d, ev in _events(op_line):
            evs.append((name, s, s + d, ev))
        if window is None:
            lo_d = min(s for _, s, _, _ in evs)
            hi_d = max(e for _, _, e, _ in evs)
        else:
            lo_d, hi_d = window
        lo = lo_d if lo is None else min(lo, lo_d)
        hi = hi_d if hi is None else max(hi, hi_d)
        for name, s, e, ev in evs:
            if e <= lo_d or s >= hi_d:
                continue
            s, e = max(s, lo_d), min(e, hi_d)
            op_iv.append((s, e))
            # the op's own name, not its operands': a fusion that reads
            # an all-gather's result is compute
            if COLLECTIVE.search(op_name(name)):
                coll_iv.append((s, e))
            else:
                comp_iv.append((s, e))
            if dev == min(devices):
                key = stable_name(name)
                ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
        # asynchronous collectives run beside the op stream, from their
        # -start to their -done: they are on a line of their own
        if ASYNC_LINE in lines:
            for name, s, d, _ in _events(lines[ASYNC_LINE]):
                if COLLECTIVE.search(op_name(name)):
                    coll_iv.extend(clip([(s, s + d)], lo_d, hi_d))
        busy = union(op_iv)
        per_device[dev] = {"busy_s": total(busy) * 1e-9}
        cu = union(coll_iv)
        coll_total += total(cu) * 1e-9
        coll_exposed += total(subtract(cu, comp_iv)) * 1e-9
        if dev == min(devices):
            mod_line = lines.get(MODULES_LINE)
            if mod_line is not None:
                for name, s, d, _ in _events(mod_line):
                    if s + d <= lo_d or s >= hi_d:
                        continue
                    base = re.sub(r"\(\d+\)$", "", name)
                    m_ = modules.setdefault(base, {"count": 0, "total_s": 0.0,
                                                   "durations_s": []})
                    m_["count"] += 1
                    m_["total_s"] += d * 1e-9
                    if len(m_["durations_s"]) < 4096:
                        m_["durations_s"].append(d * 1e-9)
            # idle gaps of the first device, by what the host was doing
            for gs, ge in subtract([(lo_d, hi_d)], busy):
                label = "host:" + index.label(gs, ge)
                gaps[label] = gaps.get(label, 0.0) + (ge - gs) * 1e-9
    n = len(devices)
    window_s = (hi - lo) * 1e-9
    spans: dict = {}
    for name, s, e, _ in host_events:
        if name.startswith("bench/") and lo <= s <= hi:
            spans.setdefault(name, []).append(((s - lo) * 1e-9,
                                               (e - s) * 1e-9))
    return {
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "devices": n, "per_device": per_device,
        "ops": ops, "modules": modules, "idle_gaps": gaps,
        "collective_s": coll_total / n,
        "collective_exposed_s": coll_exposed / n,
        "spans": spans,
    }


class _HostIndex:
    """Host events as arrays, to label thousands of gaps quickly."""

    MIN_NS = 50_000.0     # shorter events cannot explain a gap worth a name

    def __init__(self, host_events):
        import numpy as np
        keep = [(n, s, e) for n, s, e, _t in host_events
                if e - s >= self.MIN_NS]
        self.names = [k[0] for k in keep]
        self.s = np.array([k[1] for k in keep], dtype=np.float64)
        self.e = np.array([k[2] for k in keep], dtype=np.float64)

    def label(self, gs: float, ge: float) -> str:
        """The host event that covers most of the gap; among those that
        cover it equally (nested calls), the shortest, the innermost."""
        import numpy as np
        if ge - gs < self.MIN_NS:
            return "gaps_under_50us"
        if not self.names:
            return "unattributed"
        cov = np.minimum(self.e, ge) - np.maximum(self.s, gs)
        best = float(cov.max())
        if best < 0.5 * (ge - gs):
            return "unattributed"
        tied = np.flatnonzero(cov >= best * 0.9999)
        i = int(tied[np.argmin((self.e - self.s)[tied])])
        return re.sub(r"[^A-Za-z0-9_.-]+", "_", self.names[i])[:56]


def breakdown(reduced: dict, k: int = 10) -> dict:
    top = lambda d: [[n, s] for n, s in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:k]]
    return {"device_ops": top(reduced["ops"]),
            "idle_gaps": top(reduced["idle_gaps"])}
