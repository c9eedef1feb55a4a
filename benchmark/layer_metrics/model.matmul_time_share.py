"""Share of the device's busy time in matrix multiplications outside
the Pallas kernels (XLA convolution/dot fusions)."""


def read(ctx):
    s = ctx.summary
    if s and s["busy_s"] > 0:
        return 100.0 * s["categories_s"].get("matmul", 0.0) / s["busy_s"]
