"""Seconds of the restarted worker's ``ckpt.restore_copy`` spans inside
its ``trainer.restore``: the region fill and the memcpy into host arrays
(what ``assemble_s`` has been the remainder of)."""


def read(ctx):
    from benchmark import resume_path

    return resume_path.restored_sum_s(ctx, "ckpt.restore_copy")
