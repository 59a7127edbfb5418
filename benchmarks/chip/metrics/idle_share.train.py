"""Device idle share of a training cell: 1 - (union of the device's
operation intervals) / traced window, averaged over the chips."""
from chipbench.trace import busy_s


def read(ctx):
    t = ctx["trace"]
    if t.window_s <= 0 or not t.devices:
        return None
    return 100.0 * (1.0 - busy_s(t) / t.window_s)
