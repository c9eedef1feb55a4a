"""Seconds the crash flush writes shards: the ``ckpt.persist_shard``
spans inside ``ckpt-crash-flush`` (blocks, CRCs, meta, done file),
summed."""


def read(ctx):
    from benchmark import resume_path

    return resume_path.flush_sum_s(ctx, "ckpt.persist_shard")
