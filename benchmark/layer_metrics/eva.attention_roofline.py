"""The least time the chip could take for the flash-attention calls of the
traced window under the EVA mask (costs_eva.flash_attention_cost per call:
the allowed pairs, the summaries' rows among the bytes; the larger of
FLOPs over peak and bytes over peak) over the time the kernels took."""


def read(ctx):
    from benchmark import costs_eva

    s = ctx.summary
    if not s:
        return None
    job = ctx.cell["job"]
    batch = job["batch"] // s["n_devices"] or 1
    least = took = 0.0
    for kind in ("fwd", "dq", "dkv"):
        row = s["ops"].get("flash_attention." + kind)
        if row:
            flops, bytes_ = costs_eva.flash_attention_cost(
                kind, batch, ctx.sizes, job["sequence"]
            )
            each, _ = ctx.costs.roofline_seconds(flops, bytes_, ctx.peaks)
            least += row["count"] * each
            took += row["self_s"]
    if took > 0:
        return 100.0 * least / took
