"""Share of the traced window in which the device idles while the host
is inside the program's `fl/reset` span (`FedServer.reset`: a fresh
state for the next training), averaged over the cell's devices (moves
`time_to_target_s`). Absent where no reset ran in the window."""

SPAN = "fl/reset"


def read(ctx):
    if not any(s.name == SPAN for s in ctx.scopes.spans):
        return None
    return ctx.scopes.idle_shares().get(SPAN, 0.0)
