"""1 - (union of the device's op intervals) / traced window, averaged
over the chips used."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
