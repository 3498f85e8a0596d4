"""Blocking host reads of device values per training in the window: the
program's `read_round` (a read of the round counter) plus `fetch` (a
block's metrics to the host) span counts over the window, per training
run (moves `time_to_target_s`)."""


def read(ctx):
    if not ctx.histories:
        return None
    syncs = ctx.span_counts.get("read_round", 0) + ctx.span_counts.get(
        "fetch", 0)
    return syncs / len(ctx.histories)
