"""Seconds the agent spends writing the dead worker's snapshot to disk
before it restarts it: the ``ckpt-crash-flush`` span of the agent's tracer
(``DLROVER_TPU_TRACE_FILE``)."""


def read(ctx):
    spans = [e for e in ctx.agent_spans if e.get("name") == "ckpt-crash-flush"]
    if spans:
        return spans[0]["dur"] / 1e6
