"""Model FLOPs of the rounds completed in the traced window over the
window's length times the chips' bf16 peak (moves `round_s`).

The model FLOPs are the cell's family's count (`ctx.family.round_flops`,
`ctx.family.eval_flops`): for the image family, forward plus backward
for every sample trained and forward for every test image evaluated
(`bench.counts`). The peak is the bf16 one because the float32
convolutions and products run at the default precision, one bf16 pass
on the MXU."""


def read(ctx):
    if not ctx.rounds:
        return None
    fam, cfg, tr = ctx.family, ctx.config, ctx.traffic
    train = ctx.rounds * fam.round_flops(cfg, tr)
    evals = ctx.evals * fam.eval_flops(cfg, tr)
    peak = ctx.peaks["bf16_flops"] * ctx.chips
    return 100.0 * (train + evals) / (ctx.window_s * peak)
