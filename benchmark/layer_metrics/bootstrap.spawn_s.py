"""``bootstrap_timings()["spawn_s"]`` of the restarted worker: the
agent's fork to the worker's process entry."""


def read(ctx):
    starts = ctx.of("start", incarnation=1)
    if starts:
        return starts[0].get("spawn_s")
