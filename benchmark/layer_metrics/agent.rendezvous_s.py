"""Seconds from the end of the crash flush to the restarted worker's
process entry, less the fork itself (``bootstrap.spawn_s``): stopping the
old workers, the new rendezvous round, the worker's environment."""


def read(ctx):
    spans = [e for e in ctx.agent_spans if e.get("name") == "ckpt-crash-flush"]
    starts = ctx.of("start", incarnation=1)
    if spans and starts:
        flushed = (spans[0]["ts"] + spans[0]["dur"]) / 1e6
        return starts[0]["t_entry"] - flushed - starts[0].get("spawn_s", 0.0)
