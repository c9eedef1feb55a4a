"""Snapshots that landed in the window."""


def read(ctx):
    from benchmark import end_to_end

    if ctx.flush and ctx.flush.get("t_close_wall"):
        return float(len(end_to_end.snapshot_times(ctx.flush)))
