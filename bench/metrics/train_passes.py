"""How often a round trains its clients: the mean of the program's
`History.train_passes` over the rounds of the window, 1 where the
sequential round keeps each client's trained params and 2 where it
trains every client again for its angle (moves `round_s`). Absent where
the program records none (a parallel round, or a program without the
counter)."""


def read(ctx):
    passes = [p for h in ctx.histories
              for p in getattr(h, "train_passes", [])]
    if not passes:
        return None
    return sum(passes) / len(passes)
