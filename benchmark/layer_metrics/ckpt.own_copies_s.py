"""Median seconds of ``ckpt.own_copies`` — the engine's ``device_put`` of
the state into ``pinned_host``, on the loop thread inside
``save_checkpoint`` — over the snapshots that landed in the window."""


def read(ctx):
    from benchmark import program_spans

    return program_spans.cycle_median_s(ctx, "ckpt.own_copies")
