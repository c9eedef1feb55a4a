"""``engine.last_restore_stats["device_put_s"]`` of the restarted worker."""


def read(ctx):
    resumes = ctx.of("resume", incarnation=1)
    if resumes and resumes[0].get("restore"):
        return resumes[0]["restore"].get("device_put_s")
