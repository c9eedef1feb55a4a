"""Snapshots offered and not taken (or taken and not landed) during one
snapshot cycle, dispatch to landing: the rise of the program's
``ckpt.skipped`` counter, every reason together; median over the cycles
that landed in the window."""


def read(ctx):
    from benchmark import program_spans

    return program_spans.count_per_cycle(ctx, "ckpt.skipped")
