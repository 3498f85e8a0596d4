"""Device milliseconds a round under the model's `model.mla_attn` scope:
MLA's fused causal attention kernels (the forward, its rematerialization
and the dkv and dq kernels of the backward) and the layout work around
them, inside `model.mla`, from the leaf ops' name paths in the traced
window (`bench.scopes`), averaged over the cell's devices (moves
`round_s`). Absent where no op ran under the scope, as where attention
runs as einsums."""
import re

SCOPE = re.compile(r"(?:^|[/(])model\.mla_attn(?=[)/:;]|$)")


def read(ctx):
    sec = sum(o.end - o.start for o in ctx.scopes.leaf_ops()
              if SCOPE.search(o.path.split(";", 1)[0]))
    if not sec or not ctx.rounds:
        return None
    return 1e3 * sec / len(ctx.scopes.ops) / ctx.rounds
