"""Seconds from the SIGKILL (the benchmark's stamp) to the agent's
``worker.fail`` event (its wall clock, from the master's goodput.json)."""


def read(ctx):
    kills = ctx.of("kill")
    fails = [
        e for e in ctx.goodput.get("events", []) if e["kind"] == "worker.fail"
    ]
    if kills and fails:
        return fails[0]["ts"] - kills[0]["t_kill"]
