"""Share of the first device's idle time in the traced window that no
host event owns: idle_gaps["host:unattributed"] over the sum of the
idle gaps.  The measurement's own blind spot; the engine's
engine/<phase> spans are what fills it."""


def read(ctx):
    tr = ctx["trace"]
    gaps = (tr or {}).get("idle_gaps")
    if not gaps or not sum(gaps.values()):
        return None
    return 100.0 * gaps.get("host:unattributed", 0.0) / sum(gaps.values())
