"""Tokens per second over the whole snapshot cycles of the window (from
its first landing to its last): what the job makes with a snapshot always
in flight, dispatch stalls included. Against ``staging_tokens_per_s`` it
is what the dispatches cost."""


def read(ctx):
    from benchmark import end_to_end

    if ctx.flush and ctx.flush.get("t_close_wall"):
        return end_to_end.snapshotting_tokens_per_s(ctx.flush)
