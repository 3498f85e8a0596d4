"""Rounds the scanned driver runs past the target, per training that
reached it in the traced window: the server's round counter after the
training less its rounds-to-target (moves `time_to_target_s`)."""


def read(ctx):
    rows = [(rtt, ran) for rtt, ran in ctx.trainings if rtt is not None]
    if not rows:
        return None
    return sum(ran - rtt for rtt, ran in rows) / len(rows)
