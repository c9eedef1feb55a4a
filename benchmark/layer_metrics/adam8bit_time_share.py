"""Share of the device's busy time in the fused 8-bit Adam kernel."""


def read(ctx):
    s = ctx.summary
    if s and s["busy_s"] > 0 and "adam8bit" in s["ops"]:
        return 100.0 * s["ops"]["adam8bit"]["self_s"] / s["busy_s"]
