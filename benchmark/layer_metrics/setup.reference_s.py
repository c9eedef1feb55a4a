"""Seconds of the benchmark's own comparison with the plain reference
(its programs' compile or load included)."""


def read(ctx):
    refs = ctx.of("reference")
    if refs:
        return refs[0]["seconds"]
