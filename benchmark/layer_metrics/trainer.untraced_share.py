"""Share of the window's ``trainer.step`` spans that none of their child
spans covers (self time over duration): what the span table does not
name."""


def read(ctx):
    from benchmark import program_spans

    return program_spans.untraced_share(ctx)
