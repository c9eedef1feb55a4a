"""Seconds of the agent's ``agent.device_check`` span: the
``--network-check`` device check before the first rendezvous."""


def read(ctx):
    from benchmark import resume_path

    return resume_path.first_agent_s(ctx, "agent.device_check")
