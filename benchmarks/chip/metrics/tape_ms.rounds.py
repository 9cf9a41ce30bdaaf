"""Device time of the quantum tape replay per traced fused call:
operations under the program's ``tape.replay`` scope (every circuit
evaluation: Nelder-Mead's candidates, the reports, the server's evals),
on the aligned clock (``layers.py``)."""
from benchmarks.chip import layers


def read(ctx):
    return layers.per_call_ms(
        ctx, lambda n: "tape.replay" in layers.scopes_of(n))
