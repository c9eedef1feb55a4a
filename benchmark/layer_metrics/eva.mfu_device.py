"""Required FLOPs of a step of the EVA mixer (costs_eva.train_flops_per_token:
6 a matmul parameter, the pairs the mask allows once, the summaries' sums,
no recomputation) over the median time the step program takes on the
device and the chip's peak: this cell's share of the whole step's peak."""


def read(ctx):
    import statistics

    from benchmark import costs_eva

    s = ctx.summary
    if s and s["step_span_s"]:
        job = ctx.cell["job"]
        tokens = job["batch"] * job["sequence"]
        flops = tokens * costs_eva.train_flops_per_token(
            ctx.sizes, job["sequence"]
        )
        peak = ctx.peaks["bf16_flops_per_s"] * s["n_devices"]
        return 100.0 * flops / statistics.median(s["step_span_s"]) / peak
