"""Bytes over seconds of the engine's fetch of a snapshot from the host
memory space into its shared-memory segment, summed over the ``ckpt.io``
staging events of the run."""


def read(ctx):
    events = [
        e for e in (ctx.flush or {}).get("ckpt_io", [])
        if e.get("op") == "staging"
    ]
    seconds = sum(e["duration_s"] for e in events)
    if seconds > 0:
        return sum(e["bytes"] for e in events) / seconds / 1e9
