"""Share of the device's busy time in operations that only move or
re-lay data (copy, reshape, transpose, slice and their kind by opcode):
work the model's arithmetic does not require."""


def read(ctx):
    s = ctx.summary
    if s and s["busy_s"] > 0:
        moved = s["categories_s"].get("data_movement", 0.0)
        return 100.0 * moved / s["busy_s"]
