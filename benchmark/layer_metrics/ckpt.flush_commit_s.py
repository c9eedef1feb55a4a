"""Seconds the crash flush commits: the ``ckpt.flush_commit`` spans
inside ``ckpt-crash-flush`` (the done files awaited, the tracker written,
the GC), summed."""


def read(ctx):
    from benchmark import resume_path

    return resume_path.flush_sum_s(ctx, "ckpt.flush_commit")
