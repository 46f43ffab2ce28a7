"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, averaged over
the chips in use."""


def read(ctx):
    red = ctx["reduction"]
    if red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.mean_busy_s / red.window_s)
