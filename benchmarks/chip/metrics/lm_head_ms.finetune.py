"""Device time of the LM head per traced stage call: operations under the
program's ``model.head`` scope (the vocabulary projection and its
cross-entropy, forward and backward), outside the federated tail, on
the aligned clock (``layers.py``)."""
from benchmarks.chip import layers


def read(ctx):
    return layers.per_call_ms(ctx, lambda n: layers.llm_part(n) == "head")
