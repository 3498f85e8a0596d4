"""Blocking metric fetches per `FedServer.run` call in the window, from
the program's span counts (`ctx.span_counts`, `ctx.histories`)."""


def read(ctx):
    if not ctx.histories:
        return None
    return ctx.span_counts.get("fetch", 0) / len(ctx.histories)
