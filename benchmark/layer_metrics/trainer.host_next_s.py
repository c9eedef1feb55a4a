"""Seconds the loop thread spends in ``input.host_next`` (``next()`` of the
host loader under the prefetcher) during one snapshot cycle, dispatch to
landing; median over the cycles that landed in the window."""


def read(ctx):
    from benchmark import program_spans

    return program_spans.seconds_per_cycle(ctx, "input.host_next")
