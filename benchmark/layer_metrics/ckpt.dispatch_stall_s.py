"""Median seconds the training loop loses at a snapshot's dispatch
(end_to_end.dispatch_stalls: step ends around the dispatch against the
window's median step)."""


def read(ctx):
    import statistics

    from benchmark import end_to_end

    stalls = ctx.flush and end_to_end.dispatch_stalls(ctx.flush)
    if stalls:
        return statistics.median(stalls)
