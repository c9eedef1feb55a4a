"""Median host milliseconds of the loop's ``trainer.dispatch`` span over the
window's steps: the chaos site and ``train_step``'s asynchronous dispatch
(``trainer.input_wait_ms`` and the step's end stamps time the loop from
outside; this is the program's own span)."""


def read(ctx):
    from benchmark import program_spans

    value = program_spans.window_median_s(ctx, "trainer.dispatch")
    if value is not None:
        return value * 1e3
