"""Seconds of the agent's first ``agent.restart`` span: the old workers
stopped, the trace exported, the new rendezvous round and the new
workers spawned."""


def read(ctx):
    from benchmark import resume_path

    return resume_path.first_agent_s(ctx, "agent.restart")
