"""1 - (union of device operation intervals) / traced window, averaged
over the cell's chips."""


def read(ctx):
    s = ctx.summary
    if s is None or not s.busy_s:
        return None
    return 100.0 * (1.0 - s.mean_busy_s / s.window_s)
