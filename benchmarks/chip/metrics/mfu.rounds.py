"""Circuit FLOPs the traced calls' rounds need over (their time x chips x
the chip's bf16 peak): the evaluations the program reports
(branch-dependent, not speculative) x each client's examples x the
statevector work of one circuit (``flops.circuit_flops_per_eval``).  The
traced calls, not the whole window: the window's time also holds the
profiler writing its trace, where the cell traces only a part."""
from benchmarks.chip import flops


def read(ctx):
    c = ctx.counts
    per_eval = flops.circuit_flops_per_eval(c["n_qubits"], *c["gates"])
    per_call = sum(e * n for row in c["n_evals"]
                   for e, n in zip(row, c["examples"])) * per_eval
    w = ctx.window
    return 100.0 * w.traced_calls * per_call / (
        w.traced_seconds * ctx.n_chips * ctx.peaks["bf16_flops_per_s"])
