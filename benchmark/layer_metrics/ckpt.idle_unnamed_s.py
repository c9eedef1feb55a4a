"""Device idle seconds of the traced part of a snapshot cycle with no
leaf span of the program open on the loop thread: what the span table
cannot explain."""


def read(ctx):
    from benchmark import program_spans

    reduced = program_spans.idle(ctx)
    if reduced:
        return reduced["unnamed_s"]
