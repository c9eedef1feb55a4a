"""The program's own spans and counters (``SPANS`` of
``dlrover_tpu/utils/tracing.py``), read from where the program leaves them.

A worker appends one Chrome-trace event a line to
``<run's directory>/agent_trace.worker<rank>.<restart>.jsonl`` as each span
closes or counter changes (wall-clock µs; ``args`` carry ``id``, ``parent``,
``step``), so the killed incarnation's file is whole up to the kill. The
readers of ``layer_metrics/`` cut those events to the window's steps
(``end_to_end.window_steps``) or to its snapshot cycles (a snapshot's
``ckpt.snapshot`` on the loop thread to the end of its ``ckpt.publish`` on
the staging thread, tied by ``step``). While the profiler runs the same
spans sit in the ``.xplane.pb`` on the device's clock; ``idle`` lays the
device's idle gaps against them:

    python3 -m benchmark.program_spans idle <trace dir or file> <out.json>

run as a process of its own held to the CPU, as ``xplane reduce`` is. A
program without the spans (or a run without a trace) gives every reader
nothing: it returns None and the line leaves the metric out. No JAX here
outside ``idle``.
"""

import functools
import glob
import json
import os
import statistics
import subprocess
import sys

from benchmark import end_to_end

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ span files

@functools.lru_cache(maxsize=8)
def _read(path: str, _mtime: float) -> tuple:
    events = []
    with open(path) as f:
        for line in f:
            try:
                events.append(json.loads(line))
            except ValueError:      # the line a kill cut short
                continue
    return tuple(events)


def events(ctx, incarnation: int = 0, rank: int = 0) -> tuple:
    """Everything worker ``rank`` of ``incarnation`` recorded, in the
    order it was written (a span is written when it closes)."""
    found = glob.glob(os.path.join(
        ctx.cell["out"], f"*.worker{rank}.{incarnation}.jsonl"
    ))
    if not found:
        return ()
    return _read(found[0], os.path.getmtime(found[0]))


def spans(ctx, name: str = None) -> list:
    return [
        e for e in events(ctx)
        if e.get("ph") == "X" and (name is None or e["name"] == name)
    ]


def self_seconds(all_spans: list) -> dict:
    """id -> seconds of the span less its children's (by ``parent``)."""
    selfs = {e["args"]["id"]: e["dur"] / 1e6 for e in all_spans}
    for e in all_spans:
        parent = e["args"].get("parent")
        if parent in selfs:
            selfs[parent] -= e["dur"] / 1e6
    return selfs


# ------------------------------------------------------ the window's steps

def window_spans(ctx, name: str) -> list:
    """The spans ``name`` of the steps that ended inside the window."""
    steps = ctx.flush and end_to_end.window_steps(ctx.flush)
    if not steps:
        return []
    return [
        e for e in spans(ctx, name)
        if steps[0] <= e["args"].get("step", -1) <= steps[1]
    ]


def window_median_s(ctx, name: str):
    found = window_spans(ctx, name)
    if found:
        return statistics.median(e["dur"] for e in found) / 1e6


def step_less_child_s(ctx, child: str):
    """Median seconds of ``trainer.step`` less its span ``child``."""
    steps = {e["args"]["id"]: e["dur"] for e in window_spans(ctx, "trainer.step")}
    inside = [e for e in spans(ctx, child) if e["args"].get("parent") in steps]
    if not steps or not inside:
        return None
    for e in inside:
        steps[e["args"]["parent"]] -= e["dur"]
    return statistics.median(steps.values()) / 1e6


def untraced_share(ctx):
    """Self time of the window's ``trainer.step`` spans over their time."""
    steps = window_spans(ctx, "trainer.step")
    if not steps:
        return None
    selfs = self_seconds(spans(ctx))
    total = sum(e["dur"] for e in steps) / 1e6
    return 100.0 * sum(selfs[e["args"]["id"]] for e in steps) / total


# --------------------------------------------------- the snapshot cycles

def cycles(ctx) -> list:
    """(start_us, end_us, step) of the snapshots that landed in the window
    (the ones ``end_to_end.snapshot_times`` measures): from the start of
    ``ckpt.snapshot`` (the loop thread takes it) to the end of
    ``ckpt.publish`` (the staging thread made it restorable)."""
    flush = ctx.flush
    if not flush or not flush.get("t_close_wall"):
        return []
    landed = {
        step for step, t in flush["landed"]
        if flush["t_open_wall"] < t <= flush["t_close_wall"]
    }
    taken = {e["args"].get("step"): e["ts"] for e in spans(ctx, "ckpt.snapshot")}
    return [
        (taken[e["args"]["step"]], e["ts"] + e["dur"], e["args"]["step"])
        for e in spans(ctx, "ckpt.publish")
        if e["args"].get("step") in landed and e["args"]["step"] in taken
    ]


def cycle_median_s(ctx, name: str):
    """Median seconds of the span ``name`` that carries a cycle's step
    (one a snapshot: ``ckpt.own_copies``, ``ckpt.fetch``, ...)."""
    steps = {step for _, _, step in cycles(ctx)}
    found = [e["dur"] for e in spans(ctx, name) if e["args"].get("step") in steps]
    if found:
        return statistics.median(found) / 1e6


def seconds_per_cycle(ctx, name: str):
    """Median over the cycles of the seconds spent in spans ``name`` that
    began inside the cycle, on whichever thread."""
    found, named = cycles(ctx), spans(ctx, name)
    if found and named:
        return statistics.median(
            sum(e["dur"] for e in named if t0 <= e["ts"] <= t1) / 1e6
            for t0, t1, _ in found
        )


def count_per_cycle(ctx, name: str):
    """Median over the cycles of how far the cumulative counter ``name``
    (all its series together) rose inside the cycle."""
    found = cycles(ctx)
    if not found:
        return None
    totals = [
        (e["ts"], sum(e["args"].values())) for e in events(ctx)
        if e.get("ph") == "C" and e["name"] == name
    ]

    def at(t):
        return max((v for ts, v in totals if ts <= t), default=0)

    return statistics.median(at(t1) - at(t0) for t0, t1, _ in found)


# ------------------------------------------------ idle time by leaf span

def idle(ctx):
    """The ``idle`` reduction of the run's trace (made once, kept beside
    it), or None: no trace, no device in it, or no span of the program."""
    trace_dir = os.path.join(ctx.cell["out"], "trace")
    if not os.path.isdir(trace_dir):
        return None
    out = os.path.join(ctx.cell["out"], "trace_program_spans.json")
    if not os.path.isfile(out):
        r = subprocess.run(
            [sys.executable, "-m", "benchmark.program_spans", "idle",
             trace_dir, out],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
            capture_output=True, text=True, timeout=300,
        )
        if r.returncode != 0:
            print(f"benchmark: idle reduction failed:\n{r.stderr[-2000:]}",
                  file=sys.stderr, flush=True)
            with open(out, "w") as f:
                json.dump({}, f)
    with open(out) as f:
        return json.load(f) or None


def idle_seconds(ctx, name: str):
    reduced = idle(ctx)
    if reduced:
        return reduced["by_span_s"].get(name, 0.0)


def leaves(line_spans: list) -> list:
    """Of (start, end, name) spans of one thread, which nest or follow
    each other, those with no other inside them."""
    ordered = sorted(line_spans, key=lambda s: (s[0], -s[1]))
    out = []
    for i, span in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt[0] >= span[1]:
            out.append(span)
    return out


def loop_line_spans(profile, names) -> list:
    """(start_ns, end_ns, name) of the program's spans on the host thread
    that runs the training loop: the line with most ``trainer.step``."""
    best, best_steps = [], 0
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found, steps = [], 0
            for e in line.events:
                if e.name in names:
                    start = float(e.start_ns)
                    found.append((start, start + float(e.duration_ns), e.name))
                    steps += e.name == "trainer.step"
            if steps > best_steps:
                best, best_steps = found, steps
    return best


def overlaps(gaps: list, spans: list) -> dict:
    """Nanoseconds of the gaps that each span name is open for (spans of
    one thread that do not nest: the leaves)."""
    out = {}
    for g0, g1 in gaps:
        for s0, s1, name in spans:
            both = min(g1, s1) - max(g0, s0)
            if both > 0:
                out[name] = out.get(name, 0.0) + both
    return out


def reduce_idle(path: str) -> dict:
    """The device's idle gaps (``xplane.reduce_device``: the traced window
    of whole steps of the first chip, its 200 longest gaps) against the
    leaf spans of ``SPANS`` on the loop thread's line: ``by_span_s`` is
    the idle time each is open for, ``unnamed_s`` the idle time none is.
    ``by_longest_s`` is ``xplane.attribute_gaps``' answer beside it — a
    whole gap to the span open for most of it, as ``breakdown.idle_gaps``
    names gaps — which overstates one span where a gap covers several."""
    from benchmark import xplane

    try:
        from dlrover_tpu.utils.tracing import SPANS
    except ImportError:     # a program from before the spans
        return {}
    profile = xplane.load(path)
    device = None
    for plane in profile.planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            device = xplane.reduce_device(plane)
            if device:
                break
    line = loop_line_spans(profile, set(SPANS))
    if not device or not line:
        return {}
    t0, t1 = device["window_ns"]
    gaps, open_spans = device["gaps"], leaves(line)
    by_span = overlaps(gaps, open_spans)
    in_gaps = sum(g1 - g0 for g0, g1 in gaps)
    return {
        "window_s": (t1 - t0) / 1e9,
        "idle_s": (t1 - t0 - device["busy_ns"]) / 1e9,
        "attributed_s": in_gaps / 1e9,
        "unnamed_s": (in_gaps - sum(by_span.values())) / 1e9,
        "by_span_s": {name: ns / 1e9 for name, ns in by_span.items()},
        "by_longest_s": {
            name: ns / 1e9 for name, ns in
            xplane.attribute_gaps(gaps, open_spans).items()
        },
    }


def main(argv) -> int:
    if len(argv) == 3 and argv[0] == "idle":
        reduced = reduce_idle(argv[1])
        with open(argv[2], "w") as f:
            json.dump(reduced, f)
        return 0
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
