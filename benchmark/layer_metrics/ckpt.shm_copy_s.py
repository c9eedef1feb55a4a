"""Median seconds of ``ckpt.shm_copy`` — layout, segment and ``fastcopy``
of the fetched arrays into shared memory — over the snapshots that landed
in the window: with ``ckpt.fetch_s`` the bulk of ``ckpt.snapshot_s``."""


def read(ctx):
    from benchmark import program_spans

    return program_spans.cycle_median_s(ctx, "ckpt.shm_copy")
