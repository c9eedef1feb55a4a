"""Seconds from the first worker's process entry to its first sight of the
devices: the script's imports, ``init_training()`` and the TPU backend's
start."""


def read(ctx):
    starts = ctx.of("start", incarnation=0)
    if starts:
        return starts[0]["t"] - starts[0]["t_entry"]
