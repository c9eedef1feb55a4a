"""Share of the traced window in which no operation ran on the device
(averaged over the chips)."""


def read(ctx):
    s = ctx.summary
    if s and s["window_s"] > 0:
        return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
