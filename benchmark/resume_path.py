"""The resume path on the program's own spans: the agent's (its exported
ring, ``ctx.agent_spans``) and the restarted worker's (its span file,
``program_spans.events(ctx, incarnation=1)``), all on the wall clock the
benchmark's kill stamp and the worker's records share.

A program without these spans gives every reader nothing: the helpers
return None or an empty list, and the line leaves the metric out.
"""

from benchmark import program_spans


def _spans(events) -> list:
    return [e for e in events if e.get("ph") == "X"]


def agent_spans(ctx, name: str) -> list:
    """The agent's spans ``name``, in the order they closed."""
    return [e for e in _spans(ctx.agent_spans) if e["name"] == name]


def first_agent_s(ctx, name: str):
    """Seconds of the agent's first span ``name``."""
    found = agent_spans(ctx, name)
    if found:
        return found[0]["dur"] / 1e6


def under(events, root: dict, name: str) -> list:
    """The spans ``name`` that ``root`` holds, however deep (by
    ``parent``, in ``root``'s process)."""
    spans = [e for e in _spans(events) if e.get("pid") == root.get("pid")]
    by_id = {e["args"]["id"]: e for e in spans}
    out = []
    for e in spans:
        if e["name"] != name:
            continue
        parent = e["args"].get("parent")
        while parent is not None and parent != root["args"]["id"]:
            parent = by_id.get(parent, {}).get("args", {}).get("parent")
        if parent is not None:
            out.append(e)
    return out


def flush_sum_s(ctx, name: str):
    """Seconds of the spans ``name`` inside the first crash flush."""
    flushes = agent_spans(ctx, "ckpt-crash-flush")
    if flushes:
        found = under(ctx.agent_spans, flushes[0], name)
        if found:
            return sum(e["dur"] for e in found) / 1e6


def restored_sum_s(ctx, name: str):
    """Seconds of the spans ``name`` inside the restarted worker's
    ``trainer.restore``."""
    events = program_spans.events(ctx, incarnation=1)
    roots = [e for e in _spans(events) if e["name"] == "trainer.restore"]
    if roots:
        found = under(events, roots[0], name)
        if found:
            return sum(e["dur"] for e in found) / 1e6


def union_s(intervals: list, t0: float, t1: float) -> float:
    """Seconds of [t0, t1] that the (start, end) intervals cover."""
    covered, reach = 0.0, t0
    for a, b in sorted(intervals):
        a, b = max(a, reach), min(b, t1)
        if b > a:
            covered += b - a
            reach = b
    return covered


def unnamed_s(ctx):
    """Seconds from the kill to the end of the restarted worker's first
    step that no named part covers: the kill to the poll that saw it, the
    agent's ``agent.failure`` and ``agent.restart``, the worker's entry
    to its ``built`` record (bootstrap and the Trainer's build), and the
    restarted worker's outermost spans on its loop thread."""
    kills = ctx.of("kill")
    firsts = ctx.of("first_step", incarnation=1)
    starts = ctx.of("start", incarnation=1)
    builts = ctx.of("built", incarnation=1)
    failures = agent_spans(ctx, "agent.failure")
    if not (kills and firsts and starts and builts and failures):
        return None
    t0, t1 = kills[0]["t_kill"], firsts[0]["t_done"]
    named = [(t0, failures[0]["ts"] / 1e6),
             (starts[0]["t_entry"], builts[0]["t"])]
    for name in ("agent.failure", "agent.restart"):
        named += [(e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
                  for e in agent_spans(ctx, name)]
    worker = _spans(program_spans.events(ctx, incarnation=1))
    loop = [e["tid"] for e in worker if e["name"] == "trainer.restore"]
    named += [
        (e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6) for e in worker
        if "parent" not in e["args"] and (not loop or e["tid"] == loop[0])
    ]
    return (t1 - t0) - union_s(named, t0, t1)
