"""Seconds of ``agent.resume_s`` (the kill to the end of the restarted
worker's first step) that no named part covers: not the wait for the
poll that saw the exit, the agent's ``agent.failure`` and
``agent.restart``, the restarted worker's entry to its ``built`` record,
nor its outermost spans on the loop thread (``trainer.restore``,
``trainer.step``, ...)."""


def read(ctx):
    from benchmark import resume_path

    return resume_path.unnamed_s(ctx)
