"""``bootstrap_timings()["init_s"]`` of the restarted worker:
``init_training()``."""


def read(ctx):
    starts = ctx.of("start", incarnation=1)
    if starts:
        return starts[0].get("init_s")
