"""Device idle time inside program executions (gaps between operations
that lie inside an ``XLA Modules`` execution, on the aligned clock),
per chip, over the traced calls' time: idle the host does not cause."""
from benchmarks.chip import layers


def read(ctx):
    lay = layers.for_run(ctx)
    w = ctx.window
    if lay is None or w.traced_seconds <= 0:
        return None
    return 100.0 * lay.idle_in_program_s / lay.n_devices / w.traced_seconds
