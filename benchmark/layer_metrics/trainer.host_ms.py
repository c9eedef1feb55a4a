"""Median host milliseconds of a step of the window outside the wait for
the device: the loop's ``trainer.step`` span less its ``trainer.fence``
(input, dispatch, save, report, readback, callbacks and what no span
names)."""


def read(ctx):
    from benchmark import program_spans

    value = program_spans.step_less_child_s(ctx, "trainer.fence")
    if value is not None:
        return value * 1e3
