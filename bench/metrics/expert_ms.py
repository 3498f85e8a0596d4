"""Device milliseconds a round under the model's `model.moe` scope: the
MoE layers' routing, dispatch, grouped expert products (`model.moe_gmm`,
inside it), combine and shared experts, forward, remat and backward, from
the leaf ops' name paths in the traced window (`bench.scopes`), averaged
over the cell's devices (moves `round_s`). Absent where no op ran under
the scope."""
import re

SCOPE = re.compile(r"(?:^|[/(])model\.moe(?=[)/:;]|$)")


def read(ctx):
    sec = sum(o.end - o.start for o in ctx.scopes.leaf_ops()
              if SCOPE.search(o.path.split(";", 1)[0]))
    if not sec or not ctx.rounds:
        return None
    return 1e3 * sec / len(ctx.scopes.ops) / ctx.rounds
