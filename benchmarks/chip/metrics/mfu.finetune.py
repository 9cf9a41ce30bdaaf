"""Model FLOPs of the window's stage calls over (window x chips x the
chip's bf16 peak).  Model FLOPs: ``flops.model_flops`` (4 per base
weight and 6 per LoRA weight per trained token, 2 per weight per
evaluated token, attention's QK^T and AV; no recomputation)."""
from benchmarks.chip import flops


def read(ctx):
    c = ctx.counts
    calls = ctx.window.calls
    f = flops.model_flops(
        c["dims"], train_tokens=calls * c["clients"] * c["batch"] * c["seq"]
        * c["steps"], eval_tokens=calls * c["eval_rows"] * c["seq"],
        eval_rows=calls * c["eval_rows"], seq=c["seq"])
    return 100.0 * f / (ctx.window.seconds * ctx.n_chips
                        * ctx.peaks["bf16_flops_per_s"])
