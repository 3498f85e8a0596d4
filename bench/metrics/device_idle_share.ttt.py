"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices, in a cell of trainings to a target
(moves `time_to_target_s`)."""


def read(ctx):
    share = ctx.trace.idle_share()
    return None if share is None else 100.0 * share
