"""Seconds from the SIGKILL (the benchmark's stamp) to the start of the
agent's ``agent.failure`` span: the monitor's first poll that saw the
worker's exit."""


def read(ctx):
    from benchmark import resume_path

    kills = ctx.of("kill")
    failures = resume_path.agent_spans(ctx, "agent.failure")
    if kills and failures:
        return failures[0]["ts"] / 1e6 - kills[0]["t_kill"]
