"""Seconds from the SIGKILL of the worker's process group to the end of
the restarted worker's first new step (blocked until ready), on the wall
clock both share. What a failure costs; its parts are the other
``agent.*``, ``bootstrap.*``, ``accel.rebuild_s``, ``ckpt.restore_*`` and
``trainer.first_step_s``."""


def read(ctx):
    from benchmark import end_to_end

    return end_to_end.resume_s(ctx.records)
