"""Median seconds of ``ckpt.fetch`` — the staging thread's fetch of the
snapshot from ``pinned_host`` into host arrays — over the snapshots that
landed in the window (``ckpt.stage_gbps`` is its bytes over it)."""


def read(ctx):
    from benchmark import program_spans

    return program_spans.cycle_median_s(ctx, "ckpt.fetch")
