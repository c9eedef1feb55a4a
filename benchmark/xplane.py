"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle time,
operations by self time, kernels, collectives, idle gaps by host span.

    python3 -m benchmark.xplane reduce <trace dir or file> <out.json>
    python3 -m benchmark.xplane describe <trace dir or file>

Run as a process of its own (``run.py`` does, with ``JAX_PLATFORMS=cpu``):
reading a trace needs ``jax.profiler.ProfileData`` and nothing of the chip.

What a TPU v5e trace holds (looked at by hand, PR 22): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed HLO
instruction, named by the instruction's whole text and with no other
description, and whose line ``XLA Modules`` has one event per executed
program (asynchronous copies and collectives in flight sit on a line of
their own, ``Async XLA Ops``, and are not counted as the core's time). The
host's threads are lines of ``/host:CPU``; the benchmark's
``TraceAnnotation`` spans (``bench.*``) sit on the thread that runs the
training loop. A ``while`` or ``call`` event spans its body's events, so
times are self times: an event's duration less its children's.
"""

import glob
import gzip
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)"
)
CONTAINERS = ("while", "conditional", "call")
MOVES = ("copy", "reshape", "transpose", "bitcast", "slice", "concatenate",
         "dynamic-slice", "dynamic-update-slice", "pad", "broadcast")
HLO = re.compile(r"^%(?P<name>[^ ]+) = ")


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(
        os.path.join(path, "**", "*.xplane.pb*"), recursive=True
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except Exception:
        return {}


def _events(line) -> list:
    """(start_ns, end_ns, name) of a line, by start."""
    out = []
    for e in line.events:
        start = float(e.start_ns)
        out.append((start, start + float(e.duration_ns), e.name))
    out.sort(key=lambda x: (x[0], -x[1]))
    return out


def self_times(events: list) -> list:
    """Each event's duration less the events nested inside it (events of
    one line either nest or follow each other)."""
    selfs = [e[1] - e[0] for e in events]
    stack = []
    for i, (start, end, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        if stack and end <= events[stack[-1]][1]:
            selfs[stack[-1]] -= end - start
        stack.append(i)
    return [max(0.0, s) for s in selfs]


def union(intervals: list) -> list:
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        elif end > start:
            out.append([start, end])
    return out


def parse_hlo(text: str) -> dict:
    """An event of ``XLA Ops`` is named by its HLO instruction, whole:
    ``%name = <result shape> opcode(<operands>), attributes``. Returns the
    name, the opcode, the result shape, the operands and the attributes
    (anything else, e.g. a host event, comes back with no opcode)."""
    m = HLO.match(text)
    if not m:
        return {"name": text, "opcode": "", "result": "", "operands": "",
                "attributes": ""}
    rest = text[m.end():]
    # The result is one shape or a parenthesised tuple of shapes.
    depth, i = 0, 0
    while i < len(rest):
        c = rest[i]
        depth += c == "("
        depth -= c == ")"
        if c == " " and depth == 0:
            break
        i += 1
    result, call = rest[:i], rest[i + 1:]
    opcode, _, tail = call.partition("(")
    depth, j = 1, 0
    while j < len(tail) and depth:
        depth += tail[j] == "("
        depth -= tail[j] == ")"
        j += 1
    return {"name": m.group("name"), "opcode": opcode, "result": result,
            "operands": tail[:j - 1], "attributes": tail[j:]}


def _kernel(op: dict) -> str:
    """Which Pallas kernel a Mosaic call is. The calls carry no name
    (``kernel_metadata={}``), so they are told apart by what only they
    take and give: the fused 8-bit Adam has int8 moments; of the flash
    attention kernels the forward returns (o, lse), the dkv kernel two
    tensors and the dq kernel one."""
    if "s8[" in op["operands"]:
        return "adam8bit"
    if not op["result"].startswith("("):
        return "flash_attention.dq"
    if "f32[" in op["result"]:
        return "flash_attention.fwd"
    return "flash_attention.dkv"


def classify(text: str) -> tuple:
    """(category, label) of a device operation."""
    op = parse_hlo(text)
    opcode, attributes = op["opcode"], op["attributes"]
    shape = re.sub(r"\{[^}]*\}", "", op["result"])[:48]
    label = f"{op['name']} {opcode} {shape}".strip()
    if opcode in CONTAINERS:
        return "container", label
    if opcode == "custom-call" and "tpu_custom_call" in attributes:
        return "mosaic", _kernel(op)
    if COLLECTIVE.match(opcode) or "all-reduce-scatter" in attributes:
        return "collective", label
    if opcode in ("convolution", "dot") or (
        opcode == "fusion" and "kind=kOutput" in attributes
    ):
        # On the TPU a kOutput fusion is a matrix multiplication with the
        # operations on its result fused in.
        return "matmul", label
    if any(opcode.startswith(m) for m in MOVES):
        return "data_movement", label
    return "other", label


def reduce_device(plane, skip_first: int = 1) -> dict:
    lines = {line.name: line for line in plane.lines}
    if OPS_LINE not in lines:
        return {}
    ops = _events(lines[OPS_LINE])
    if not ops:
        return {}
    modules = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
    # The step program: the module that takes most of the device's time.
    by_module = {}
    for start, end, name in modules:
        by_module[name] = by_module.get(name, 0.0) + end - start
    main = max(by_module, key=by_module.get) if by_module else None
    steps = [(s, e) for s, e, n in modules if n == main]
    # Whole steps only: the first in a trace began before the profiler was
    # ready (the device idles while it starts).
    steps = steps[skip_first:] if len(steps) > skip_first + 1 else steps
    if steps:
        t0, t1 = steps[0][0], steps[-1][1]
    else:
        t0, t1 = ops[0][0], max(e[1] for e in ops)
    inside = [e for e in ops if e[0] >= t0 and e[1] <= t1]
    selfs = self_times(inside)
    table, categories = {}, {}
    for (_, _, name), self_ns in zip(inside, selfs):
        category, label = classify(name)
        row = table.setdefault(
            label, {"self_ns": 0.0, "count": 0, "category": category}
        )
        row["self_ns"] += self_ns
        row["count"] += 1
        categories[category] = categories.get(category, 0.0) + self_ns
    busy = union([(e[0], e[1]) for e in inside])
    gaps, cursor = [], t0
    for start, end in busy:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = end
    if t1 > cursor:
        gaps.append((cursor, t1))
    return {
        "plane": plane.name, "module": main, "window_ns": [t0, t1],
        "busy_ns": sum(e - s for s, e in busy),
        "steps": [[s, e] for s, e in steps],
        "ops": table, "categories_ns": categories,
        "gaps": sorted(gaps, key=lambda g: g[0] - g[1])[:200],
    }


def host_spans(profile) -> list:
    """(start_ns, end_ns, name) of the spans on host threads that can name
    an idle gap: the benchmark's own first, the runtime's beside them."""
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith(("bench.", "Pjit")) or any(
                    word in name
                    for word in ("Execute", "TransferTo", "TransferFrom")
                ):
                    start = float(e.start_ns)
                    spans.append((start, start + float(e.duration_ns), name))
    return spans


def attribute_gaps(gaps: list, spans: list) -> dict:
    """Idle nanoseconds by the host span open for most of each gap; the
    benchmark's spans win over the runtime's."""
    out = {}
    for g0, g1 in gaps:
        best, best_key = "host: no span", (0, 0.0)
        for s0, s1, name in spans:
            overlap = min(g1, s1) - max(g0, s0)
            if overlap <= 0:
                continue
            key = (1 if name.startswith("bench.") else 0, overlap)
            if key > best_key:
                best, best_key = name, key
        out[best] = out.get(best, 0.0) + g1 - g0
    return out


def reduce(path: str) -> dict:
    profile = load(path)
    devices = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            d = reduce_device(plane)
            if d:
                devices.append(d)
    if not devices:
        return {"devices": [], "summary": None}
    spans = host_spans(profile)
    n = len(devices)
    window_ns = sum(d["window_ns"][1] - d["window_ns"][0] for d in devices) / n
    busy_ns = sum(d["busy_ns"] for d in devices) / n
    ops, categories = {}, {}
    for d in devices:
        for label, row in d["ops"].items():
            agg = ops.setdefault(
                label, {"self_s": 0.0, "count": 0, "category": row["category"]}
            )
            agg["self_s"] += row["self_ns"] / 1e9 / n
            agg["count"] += row["count"] / n
        for c, ns in d["categories_ns"].items():
            categories[c] = categories.get(c, 0.0) + ns / 1e9 / n
    gaps = attribute_gaps(devices[0]["gaps"], spans)
    steps = devices[0]["steps"]
    summary = {
        "n_devices": n, "window_s": window_ns / 1e9, "busy_s": busy_ns / 1e9,
        "module": devices[0]["module"], "steps": len(steps),
        "step_span_s": [(e - s) / 1e9 for s, e in steps],
        "categories_s": categories, "ops": ops,
        "device_ops": [
            [label, row["self_s"]] for label, row in sorted(
                ops.items(), key=lambda kv: -kv[1]["self_s"]
            )[:10]
        ],
        "idle_gaps": [
            [name, ns / 1e9] for name, ns in sorted(
                gaps.items(), key=lambda kv: -kv[1]
            )[:10]
        ],
    }
    for d in devices:  # the summary carries what the readers need
        d.pop("ops"), d.pop("gaps")
    return {"devices": devices, "summary": summary}


def describe(path: str, top: int = 40) -> dict:
    """What is in a trace: planes, lines, and per line the events that
    take most time with a sample of their stats. For reading by hand."""
    profile = load(path)
    out = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            totals, sample, count = {}, {}, 0
            for e in line.events:
                count += 1
                totals[e.name] = totals.get(e.name, 0.0) + float(e.duration_ns)
                if e.name not in sample:
                    sample[e.name] = {
                        k: (v if isinstance(v, (int, float)) else str(v)[:300])
                        for k, v in _stats(e).items()
                    }
            names = sorted(totals, key=totals.get, reverse=True)[:top]
            lines.append({
                "line": line.name, "events": count,
                "top": [
                    {"name": n, "total_ms": totals[n] / 1e6,
                     "stats": sample[n]} for n in names
                ],
            })
        out.append({"plane": plane.name, "lines": lines})
    return {"file": find_xplane(path), "planes": out}


def main(argv) -> int:
    if len(argv) >= 2 and argv[0] == "describe":
        json.dump(describe(argv[1]), sys.stdout, indent=1)
        return 0
    if len(argv) == 3 and argv[0] == "reduce":
        with open(argv[2], "w") as f:
            json.dump(reduce(argv[1]), f)
        return 0
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
