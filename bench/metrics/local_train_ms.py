"""Device milliseconds a round under the program's `fl.local_train`
scope: the clients' local SGD steps, from the leaf ops' name paths in
the traced window (`bench.scopes`), averaged over the cell's devices
(moves `round_s`)."""

SCOPE = "fl.local_train"


def read(ctx):
    sec = ctx.scopes.scope_seconds().get(SCOPE)
    if not sec or not ctx.rounds:
        return None
    return 1e3 * sec / len(ctx.scopes.ops) / ctx.rounds
