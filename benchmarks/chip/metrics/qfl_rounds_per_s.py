"""Federated rounds of the window's completed calls over the time from
the window's start to the end of the last call."""


def read(ctx):
    return sum(ctx.window.work) / ctx.window.seconds
