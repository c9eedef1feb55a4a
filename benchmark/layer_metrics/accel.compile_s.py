"""Seconds in the backend compiler (or the cache's loader) up to the
window's open, from ``jax.monitoring``."""


def read(ctx):
    if ctx.flush and ctx.flush.get("open_compiles"):
        return ctx.flush["open_compiles"]["backend_compile_s"]
