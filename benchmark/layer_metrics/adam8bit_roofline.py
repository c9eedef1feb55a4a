"""The least time the chip could take for the 8-bit Adam updates of the
traced window (costs.adam8bit_cost over all parameters, once a step) over
the time the kernel took."""


def read(ctx):
    s = ctx.summary
    row = s and s["ops"].get("adam8bit")
    if row and s["steps"]:
        size = {"bfloat16": 2, "float32": 4}[ctx.cell["job"]["param_dtype"]]
        flops, bytes_ = ctx.costs.adam8bit_cost(
            ctx.sizes["params"], size, size
        )
        least, _ = ctx.costs.roofline_seconds(flops, bytes_, ctx.peaks)
        return 100.0 * s["steps"] * least / row["self_s"]
