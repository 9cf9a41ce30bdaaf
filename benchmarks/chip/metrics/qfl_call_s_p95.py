"""The 95th percentile of the wall time of every call in the window."""
from benchmarks.chip.harness import percentile


def read(ctx):
    return percentile(ctx.window.durations, 95)
