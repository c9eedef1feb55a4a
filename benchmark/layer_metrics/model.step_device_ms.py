"""Median milliseconds the step program takes on the device, start to end
(the ``XLA Modules`` events of the traced window)."""


def read(ctx):
    import statistics

    s = ctx.summary
    if s and s["step_span_s"]:
        return 1e3 * statistics.median(s["step_span_s"])
