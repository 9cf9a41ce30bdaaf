"""The least time the chip could take for the matmuls the window's stage
calls execute (``flops.executed_matmuls``: remat's recomputed forward and
the LoRA einsums included; each matmul at the larger of its FLOP and
byte bound, which at these shapes is the FLOP bound for the base
projections) over the device time of the trace's matmul-class
operations, summed over chips."""
from benchmarks.chip import flops


def read(ctx):
    s = ctx.summary
    t = s.class_total("matmul") if s is not None else 0.0
    if t <= 0:
        return None
    c = ctx.counts
    rows = flops.executed_matmuls(
        c["dims"], clients=c["clients"], batch=c["batch"], seq=c["seq"],
        steps=c["steps"], eval_rows=c["eval_rows"])
    least = ctx.window.traced_calls * flops.least_time_s(
        rows, ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
