"""Share of the device's busy time in the three flash-attention kernels."""


def read(ctx):
    s = ctx.summary
    if s and s["busy_s"] > 0:
        took = sum(
            row["self_s"] for name, row in s["ops"].items()
            if name.startswith("flash_attention.")
        )
        if took > 0:
            return 100.0 * took / s["busy_s"]
