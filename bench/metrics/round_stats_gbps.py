"""Bandwidth the round_stats kernels achieve: the bytes their calls need
(`bench.counts`) over their summed device time, in GB/s (moves
`round_s`). Absent where no round_stats kernel ran (the XLA fallback
below K*N = 2**17).

Not a share of the HBM roofline: in the compiled round the (K, N) wire
buffer sits in memory space S(1), on the chip, and the calls run well
above the HBM peak (`PERF.md`), so that peak bounds nothing here."""


def read(ctx):
    return ctx.counts.kernel_gbps(ctx.trace, "stats", ctx.clients,
                                  ctx.params,
                                  ctx.traffic["fl"].get("group_size", 512))
