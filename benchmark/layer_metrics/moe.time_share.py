"""Share of the device's busy time in the routed path of the held-experts
layers: the grouped matmuls (the cell's only Mosaic calls that are neither
flash attention nor the optimizer: ``mosaic.unknown``) and the operations
whose result has the rows of the pair buffer (the gather of the buffer's
rows, the activation between the matmuls). The router's matmul, the rounds
that balance the choice's bias, the sort of the pairs and the scatter-add's
result have other shapes and are not in it."""


def read(ctx):
    from benchmark import xplane

    s = ctx.summary
    if not (s and s["busy_s"] > 0):
        return None
    rows = f"[{ctx.cell['job']['moe']['pair_buffer']},"
    took = sum(
        row["self_s"] for label, row in s["ops"].items()
        if label == xplane.UNKNOWN
        or (row["category"] != "mosaic" and rows in label)
    )
    if took > 0:
        return 100.0 * took / s["busy_s"]
