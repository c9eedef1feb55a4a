"""Seconds the loop thread spends in ``input.device_put`` (the
prefetcher's ``device_put`` of the next batch) during one snapshot cycle,
dispatch to landing; median over the cycles that landed in the window."""


def read(ctx):
    from benchmark import program_spans

    return program_spans.seconds_per_cycle(ctx, "input.device_put")
