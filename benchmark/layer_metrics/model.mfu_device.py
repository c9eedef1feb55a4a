"""Required FLOPs of a step (costs.train_flops_per_token: causal attention
once, no recomputation) over the median time the step program takes on
the device and the peak of the chips the cell uses."""


def read(ctx):
    import statistics

    s = ctx.summary
    if s and s["step_span_s"]:
        job = ctx.cell["job"]
        tokens = job["batch"] * job["sequence"]
        flops = tokens * ctx.costs.train_flops_per_token(
            ctx.sizes, job["sequence"]
        )
        peak = ctx.peaks["bf16_flops_per_s"] * s["n_devices"]
        return 100.0 * flops / statistics.median(s["step_span_s"]) / peak
