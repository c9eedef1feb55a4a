"""The least time the chip could take for the flash-attention calls of the
traced window (costs.flash_attention_cost per call, the larger of FLOPs
over peak and bytes over peak) over the time the kernels took."""


def read(ctx):
    s = ctx.summary
    if not s:
        return None
    job, z = ctx.cell["job"], ctx.sizes
    batch = job["batch"] // s["n_devices"] or 1
    least = took = 0.0
    for kind in ("fwd", "dq", "dkv"):
        row = s["ops"].get("flash_attention." + kind)
        if row:
            flops, bytes_ = ctx.costs.flash_attention_cost(
                kind, batch, z["heads"], job["sequence"], z["head_dim"]
            )
            each, _ = ctx.costs.roofline_seconds(flops, bytes_, ctx.peaks)
            least += row["count"] * each
            took += row["self_s"]
    if took > 0:
        return 100.0 * least / took
