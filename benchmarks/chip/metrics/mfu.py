"""Model FLOP utilization of the whole round: model FLOPs per token (no
recomputation) times the traced window's tokens per second, over the chips'
bf16 peak."""

from benchmarks.chip import flops


def read(ctx):
    per_token = flops.flops_per_token(ctx["config"], ctx["traffic"]["seq"])
    peak = ctx["chips"] * ctx["peak"]["bf16_flops"]
    return 100.0 * per_token * ctx["tokens_per_s"] / peak
