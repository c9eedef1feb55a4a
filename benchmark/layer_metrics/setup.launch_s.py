"""Seconds from the benchmark's start to the first worker's process
entry: launcher, master, device check (where the job asks for it), fork
server."""


def read(ctx):
    starts = ctx.of("start", incarnation=0)
    if starts:
        return starts[0]["t_entry"] - ctx.t_start
