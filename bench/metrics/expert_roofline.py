"""The grouped expert products' share of their roofline, in percent
(moves `round_s`): the least time the chip could take for them,
max(F / bf16 peak, bytes / HBM bandwidth), over their device time, the
leaf ops under the model's `model.moe_gmm` scope in the traced window
(`bench.scopes`), per device.

F and the bytes come from the program's row counter (the token-expert
rows through the held experts' products, `History.expert_rows` a round
and `History.eval_expert_rows` an eval) and the family's counting
functions (`gmm_flops`, `gmm_bytes`, `gmm_calls`). Absent where no op
ran under the scope or the program counts no rows."""
import re

SCOPE = re.compile(r"(?:^|[/(])model\.moe_gmm(?=[)/:;]|$)")


def read(ctx):
    fam = ctx.family
    if not hasattr(fam, "gmm_flops"):
        return None
    sec = sum(o.end - o.start for o in ctx.scopes.leaf_ops()
              if SCOPE.search(o.path.split(";", 1)[0]))
    hists = ctx.histories
    train = sum(sum(getattr(h, "expert_rows", [])) for h in hists)
    evals = sum(sum(getattr(h, "eval_expert_rows", [])) for h in hists)
    if not sec or not train:
        return None
    sec /= len(ctx.scopes.ops)
    calls = fam.gmm_calls(ctx.config, ctx.traffic, ctx.rounds, ctx.evals)
    flops = fam.gmm_flops(ctx.config, train, evals)
    nbytes = fam.gmm_bytes(ctx.config, train, evals, *calls)
    least = max(flops / (ctx.peaks["bf16_flops"]),
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / sec
