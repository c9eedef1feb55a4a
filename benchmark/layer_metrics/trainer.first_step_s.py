"""Seconds from the end of the restore to the end of the restarted
worker's first step (data pipeline start, the step program's load from
the compile cache, the step)."""


def read(ctx):
    resumes = ctx.of("resume", incarnation=1)
    firsts = ctx.of("first_step", incarnation=1)
    if resumes and firsts:
        return firsts[0]["t_done"] - resumes[0]["t"]
