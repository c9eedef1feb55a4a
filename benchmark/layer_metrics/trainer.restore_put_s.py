"""Seconds of the restarted worker's ``trainer.restore_put`` span: the
``jax.device_put`` of the restored state onto the step's shardings, as
dispatched (the transfer shows in the first step's fence)."""


def read(ctx):
    from benchmark import resume_path

    return resume_path.restored_sum_s(ctx, "trainer.restore_put")
