"""Seconds of the agent's ``agent.report_failure`` span: the WORKER_FAIL
event and the report_failure RPC to the master, before the crash flush."""


def read(ctx):
    from benchmark import resume_path

    return resume_path.first_agent_s(ctx, "agent.report_failure")
