"""Median host milliseconds of the loop's ``trainer.report`` span over the
window's steps: the ``report_global_step`` RPC to the master and
``report_training_metrics`` — the control plane's one touch on the step
path."""


def read(ctx):
    from benchmark import program_spans

    value = program_spans.window_median_s(ctx, "trainer.report")
    if value is not None:
        return value * 1e3
