"""Seconds of ``Trainer.restore()`` in the restarted worker (the
benchmark's clock around the call)."""


def read(ctx):
    resumes = ctx.of("resume", incarnation=1)
    if resumes:
        return resumes[0]["restore_s"]
