"""Model FLOPs of the rounds completed in the traced window over the
window's length times the chips' bf16 peak (moves `round_s`).

The model FLOPs are forward plus backward for every sample trained and
forward for every test image evaluated (`bench.counts`); the peak is the
bf16 one because the float32 convolutions and products run at the
default precision, one bf16 pass on the MXU."""


def read(ctx):
    if not ctx.rounds:
        return None
    c, tr = ctx.counts, ctx.traffic
    train = ctx.rounds * c.round_flops(ctx.config, tr, eval_round=False)
    evals = ctx.evals * tr["data"]["num_test"] * c.forward_flops(ctx.config)
    peak = ctx.peaks["bf16_flops"] * ctx.chips
    return 100.0 * (train + evals) / (ctx.window_s * peak)
