"""Share of the traced part of a snapshot-cycle window in which no
operation ran on the device: the trace starts where a cycle does, at a
snapshot's dispatch."""


def read(ctx):
    s = ctx.summary
    if s and s["window_s"] > 0:
        return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
