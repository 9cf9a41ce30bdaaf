"""Device time of batched Nelder-Mead's own work per traced fused call:
operations under ``nm.init`` or ``nm.iterate`` and not under
``tape.replay`` (simplex sorting, candidates, branch selection, the
loop's control), on the aligned clock (``layers.py``)."""
from benchmarks.chip import layers


def _nm_self(op_name):
    s = layers.scopes_of(op_name)
    return "tape.replay" not in s and any(x.startswith("nm.") for x in s)


def read(ctx):
    return layers.per_call_ms(ctx, _nm_self)
