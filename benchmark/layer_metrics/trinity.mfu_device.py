"""Required FLOPs of a step of one chip's share of Trinity
(costs_trinity.train_flops_per_token: 6 a matmul parameter a token passes
through, the routed experts at the share a uniform router sends to the
experts held, the pairs each layer's own mask allows once, no recomputation
and no padding rows) over the median time the step program takes on the
device and the chip's peak: this cell's share of the whole step's peak."""


def read(ctx):
    import statistics

    from benchmark import costs_trinity

    s = ctx.summary
    if s and s["step_span_s"]:
        job = ctx.cell["job"]
        tokens = job["batch"] * job["sequence"]
        flops = tokens * costs_trinity.train_flops_per_token(
            ctx.sizes, job["sequence"]
        )
        peak = ctx.peaks["bf16_flops_per_s"] * s["n_devices"]
        return 100.0 * flops / statistics.median(s["step_span_s"]) / peak
