"""Seconds the first worker spends in ``Trainer(...)`` until the weights
are on the device: backend start, ``auto_accelerate``, the init program."""


def read(ctx):
    built = ctx.of("built", incarnation=0)
    if built:
        return built[0]["build_s"]
