"""The largest ``peak_bytes_in_use`` over the cell's devices, read after
the window and before the reference allocates: set-up and window."""


def read(ctx):
    return ctx.peak_bytes / 1e9
