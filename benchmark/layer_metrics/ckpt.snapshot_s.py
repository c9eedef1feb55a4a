"""Median seconds from a snapshot's dispatch to its being restorable
(``engine.cached_step`` reaches it), over the snapshots that landed in
the window: the work a kill can cost."""


def read(ctx):
    from benchmark import end_to_end

    if ctx.flush and ctx.flush.get("t_close_wall"):
        return end_to_end.snapshot_s(ctx.flush)
