"""From the worker's records to the end-to-end metrics and ``correct``.

Pure Python, used by the parent. A stamp ``(step, perf, wall, loss_lag1)``
is taken in ``on_step_end(step)`` right after the loop's lag-1 fence: it
is the end of step ``step - 1`` on the device, and ``loss_lag1`` is that
step's loss.
"""

import math
import statistics

from benchmark import compare, records

FLUSHES = ("done", "kill")


def last_flush(recs: list, incarnation: int):
    """The fullest record an incarnation left: its last."""
    found = [
        r for r in recs
        if r["event"] in FLUSHES and r["incarnation"] == incarnation
    ]
    return found[-1] if found else None


def window_stamps(flush: dict) -> list:
    """The step ends that fall in the window (a window closed by the
    clock ends in a stamp of its own: the last step's end, drained)."""
    t_open, t_close = flush["t_open"], flush["t_close"]
    if t_open is None or t_close is None:
        return []
    return [s for s in flush["stamps"] if t_open < s[1] <= t_close]


def window_steps(flush: dict):
    """(first, last) of the steps whose end falls in the window."""
    inside = window_stamps(flush)
    return (inside[0][0] - 1, inside[-1][0] - 1) if inside else None


def _rate(flush: dict, stamps: list):
    if len(stamps) < 2:
        return None
    steps = stamps[-1][0] - stamps[0][0]
    return steps * flush["tokens_per_step"] / (stamps[-1][1] - stamps[0][1])


def tokens_per_s(flush: dict):
    """Tokens of the steps whose end falls in the window, over the time
    from the first such end to the last."""
    return _rate(flush, window_stamps(flush))


def staging_tokens_per_s(flush: dict):
    """Tokens per step over the median time between step ends in the
    window: the rate between snapshot dispatches, while one stages."""
    inside = window_stamps(flush)
    gaps = [b[1] - a[1] for a, b in zip(inside, inside[1:])]
    if len(gaps) < 3:
        return None
    return flush["tokens_per_step"] / statistics.median(gaps)


def snapshotting_tokens_per_s(flush: dict):
    """Tokens per second over the whole snapshot cycles of the window:
    the step ends between its first landing and its last."""
    landings = [
        t for _, t in flush["landed"]
        if flush["t_open_wall"] < t <= flush["t_close_wall"]
    ]
    if len(landings) < 2:
        return None
    return _rate(flush, [
        s for s in flush["stamps"] if landings[0] < s[2] <= landings[-1]
    ])


def snapshot_times(flush: dict) -> list:
    """Seconds from a snapshot's dispatch (the ``save_checkpoint`` call
    that took it, right after the step that made the state was
    dispatched) to its being restorable, for the snapshots that landed
    in the window."""
    dispatched = dict(flush["dispatched"])
    return [
        t_land - dispatched[step] for step, t_land in flush["landed"]
        if flush["t_open_wall"] < t_land <= flush["t_close_wall"]
        and step in dispatched
    ]


def dispatch_stalls(flush: dict) -> list:
    """Seconds the loop loses at each snapshot dispatched in the window:
    how much longer than the window's median step the four steps around
    the dispatch took (the device-to-host copy of the state runs on the
    device's stream before the next step)."""
    inside = window_stamps(flush)
    gaps = {b[0]: b[1] - a[1] for a, b in zip(inside, inside[1:])}
    if not gaps:
        return []
    typical = statistics.median(gaps.values())
    return [
        sum(gaps[n] - typical for n in range(step, step + 4) if n in gaps)
        for step, t in flush["dispatched"]
        if flush["t_open_wall"] <= t <= flush["t_close_wall"]
    ]


def snapshot_s(flush: dict):
    times = snapshot_times(flush)
    return statistics.median(times) if times else None


def resume_s(recs: list):
    """SIGKILL of the worker's group to the end of the restarted worker's
    first new step, on the wall clock both share."""
    kills = records.of(recs, "kill")
    firsts = records.of(recs, "first_step", incarnation=1)
    if not kills or not firsts:
        return None
    return firsts[0]["t_done"] - kills[0]["t_kill"]


def first_step_loss(flush: dict):
    for step, _, _, loss_lag1 in flush["stamps"]:
        if step == 2:
            return loss_lag1
    return None


def judge(cell: dict, recs: list) -> dict:
    """``correct``, ``attempted``, ``failed`` and the reasons."""
    job = cell["job"]
    flush = last_flush(recs, 0)
    why = []
    if flush is None or flush["t_close"] is None:
        return {"correct": False, "attempted": 0, "failed": 0,
                "why": ["the window never closed"]}
    # (a), (b): the reference comparison made in set-up
    refs = records.of(recs, "reference")
    if not refs:
        why.append("no reference comparison was recorded")
    else:
        why += compare.judge_reference(refs[0], first_step_loss(flush))
    # (c): finite losses, nothing compiled inside the window
    inside = window_stamps(flush)
    bad = [s for s in inside if s[3] is None or not math.isfinite(s[3])]
    if bad:
        why.append(f"{len(bad)} non-finite losses in the window")
    opened, closed = flush["open_compiles"], flush["close_compiles"]
    if closed["compile_requests"] != opened["compile_requests"]:
        why.append(
            f"{closed['compile_requests'] - opened['compile_requests']} "
            "programs compiled or loaded inside the window"
        )
    attempted = inside[-1][0] - inside[0][0] if inside else 0
    failed = len(bad)
    # snapshots: dispatched ones must land (the one in flight at the end
    # of the records is not yet due)
    if job["checkpoint"]["enabled"]:
        landed = {s for s, _ in flush["landed"]}
        due = [s for s, t in flush["dispatched"]
               if flush["t_open_wall"] <= t <= flush["t_close_wall"]]
        last_landed = max(landed, default=-1)
        lost = [s for s in due if s not in landed and s < last_landed]
        if not landed:
            lost = due
            why.append("no snapshot landed")
        elif lost:
            why.append(f"snapshots of steps {lost} never landed")
        attempted += len(due)
        failed += len(lost)
    # (d): the resume
    if job.get("kill"):
        attempted += 1
        kills = records.of(recs, "kill")
        resumes = records.of(recs, "resume", incarnation=1)
        firsts = records.of(recs, "first_step", incarnation=1)
        resumed = False
        if not kills:
            why.append("the worker was never killed")
        elif not resumes or not firsts:
            why.append("the killed worker did not resume")
        else:
            kill, res = kills[0], resumes[0]
            if res["step"] != kill["snapshot_step"]:
                why.append(
                    f"resumed at step {res['step']}, the snapshot was of "
                    f"step {kill['snapshot_step']}"
                )
            elif not kill["fingerprint"] or (
                res["fingerprint"] != kill["fingerprint"]
            ):
                why.append("the restored state's fingerprint differs from "
                           "the one recorded before the kill")
            else:
                resumed = True
            done = last_flush(recs, 1)
            if done is None:
                why.append("the restarted worker did not finish")
                resumed = False
            elif done["cache_misses"]:
                why.append(
                    f"the restart compiled {done['cache_misses']} new "
                    "programs"
                )
        failed += 0 if resumed else 1
    return {"correct": not why, "attempted": attempted, "failed": failed,
            "why": why}


def metrics(cell: dict, recs: list, t_start: float) -> dict:
    """Every end-to-end metric of the cell that the records can give."""
    flush = last_flush(recs, 0)
    out = {}
    if flush is None or flush["t_open"] is None:
        return out
    values = {
        "setup_s": flush["t_open_wall"] - t_start,
        "tokens_per_s": tokens_per_s(flush),
        "staging_tokens_per_s": staging_tokens_per_s(flush),
    }
    for m in cell["end_to_end"]:
        value = values.get(m["name"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
