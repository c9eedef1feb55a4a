"""Seconds the crash flush waits for locks: the ``ckpt.flush_lock_wait``
spans inside ``ckpt-crash-flush`` (the saver's flush mutex and each
shard's lock), summed."""


def read(ctx):
    from benchmark import resume_path

    return resume_path.flush_sum_s(ctx, "ckpt.flush_lock_wait")
