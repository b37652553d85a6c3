"""Share of the traced window in which a collective ran on a device
and no compute ran beside it, averaged over the chips."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
