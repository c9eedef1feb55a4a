"""The least time the chip could take for the flash-attention calls of the
traced window under each layer's own mask (costs_trinity.flash_attention_cost
per call: the allowed pairs of a sliding or of a full layer; the larger of
FLOPs over peak and bytes over peak) over the time the kernels took. A pass
over the layers makes one call a layer, so the calls of a kind are taken in
the layers' mix (4 sliding to 1 full)."""


def read(ctx):
    from benchmark import costs_trinity

    s = ctx.summary
    if not s:
        return None
    job = ctx.cell["job"]
    batch = job["batch"] // s["n_devices"] or 1
    least = took = 0.0
    for kind in ("fwd", "dq", "dkv"):
        row = s["ops"].get("flash_attention." + kind)
        if row:
            calls = costs_trinity.flash_attention_step_cost(
                kind, batch, ctx.sizes, job["sequence"]
            )
            a_pass = sum(
                ctx.costs.roofline_seconds(flops, bytes_, ctx.peaks)[0]
                for flops, bytes_ in calls
            )
            least += row["count"] / len(calls) * a_pass
            took += row["self_s"]
    if took > 0:
        return 100.0 * least / took
