"""Model FLOP/s utilization of the train step: the model FLOPs per token
(bench/flops/<block>.py) times the untraced window's tokens per second,
over the chips' summed peak FLOP/s."""
UNIT = "%"
MOVES = "tokens_per_s"


def read(ctx):
    per_token = ctx.flops.flops_per_token(ctx.config, ctx.cell["seq_len"])
    peak = ctx.cell["chips"] * ctx.peak["flops_per_s"]
    return 100.0 * per_token * ctx.tokens_per_s / peak
