"""Device time of the stage's federated tail per traced call: operations
under ``llm.fedavg`` (FedAvg teacher, distillation blend) and
``llm.eval`` (the label-head evaluations), on the aligned clock
(``layers.py``)."""
from benchmarks.chip import layers


def read(ctx):
    return layers.per_call_ms(ctx, lambda n: layers.llm_part(n) == "tail")
