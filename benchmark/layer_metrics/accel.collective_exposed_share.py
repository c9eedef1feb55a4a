"""Share of the traced window a chip spends in collective operations
while nothing else runs on it (operations of one chip's line run one after
another, so a collective's own time is exposed time)."""


def read(ctx):
    s = ctx.summary
    if s and s["window_s"] > 0 and "collective" in s["categories_s"]:
        return 100.0 * s["categories_s"]["collective"] / s["window_s"]
