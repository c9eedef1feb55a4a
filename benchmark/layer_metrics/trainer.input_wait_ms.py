"""Median host milliseconds a step of the window spends in
``next(batch)``."""


def read(ctx):
    import statistics

    waits = ctx.window_slice("input_wait")
    if waits:
        return statistics.median(waits) * 1e3
