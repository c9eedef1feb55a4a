"""Seconds from the end of the reference comparison to the window's open:
the step program's compile or load and the warm-up steps."""


def read(ctx):
    refs = ctx.of("reference")
    if refs and ctx.flush and ctx.flush.get("t_open_wall"):
        return ctx.flush["t_open_wall"] - refs[0]["t"]
