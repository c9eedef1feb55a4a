"""The least time the chip could take for the grouped matmuls of the traced
window (costs_trinity.grouped_matmul_cost a call, over all the rows of the
pair buffer, padding with the rest: every call contracts or produces
buffer x hidden x expert width) over the time they took. The calls are the
cell's Mosaic calls that are neither flash attention nor the optimizer
(``mosaic.unknown``)."""


def read(ctx):
    from benchmark import costs_trinity, xplane

    s = ctx.summary
    row = s and s["ops"].get(xplane.UNKNOWN)
    if row and row["self_s"] > 0:
        flops, bytes_ = costs_trinity.grouped_matmul_cost(
            ctx.sizes, ctx.cell["job"]["moe"]["pair_buffer"]
        )
        each, _ = ctx.costs.roofline_seconds(flops, bytes_, ctx.peaks)
        return 100.0 * row["count"] * each / row["self_s"]
