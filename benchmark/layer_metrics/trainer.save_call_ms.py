"""Median host milliseconds a step of the window spends in
``checkpointer.save_checkpoint`` (offered every step; mostly a skip)."""


def read(ctx):
    import statistics

    calls = ctx.window_slice("save_call")
    if calls:
        return statistics.median(calls) * 1e3
