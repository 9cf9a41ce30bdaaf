"""Set-up time: from the process's start to the window's (imports, device
start, inputs and weights, compilation or loading from the cache, the
program's first call)."""


def read(ctx):
    return ctx.setup_s
