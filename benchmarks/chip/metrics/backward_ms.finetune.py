"""Device time of the decoder's backward pass per traced stage call:
operations whose op_name holds ``transpose(`` (backward) or
``rematted_computation`` (remat's recompute), the LM head and the
federated tail excluded, on the aligned clock (``layers.py``)."""
from benchmarks.chip import layers


def read(ctx):
    return layers.per_call_ms(ctx,
                              lambda n: layers.llm_part(n) == "backward")
