"""Seconds the restarted worker spends in ``Trainer(...)`` until its
template state is on the device: backend start, ``auto_accelerate``, the
init program (a cache hit)."""


def read(ctx):
    built = ctx.of("built", incarnation=1)
    if built:
        return built[0]["build_s"]
