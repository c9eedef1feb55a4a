"""``dlrover-tpu timeline`` — render the merged job event log.

Reads events from a master state dir (snapshot + WAL, the durable form
of the EventLog) and/or the goodput JSON a master writes on stop where
``DLROVER_TPU_GOODPUT_JSON`` names a path (``ObservabilityPlane.
dump_json``), merges them with any per-process Chrome trace files, and
renders:

- a human-readable incident timeline on stdout (one line per event,
  relative timestamps, plus the rebuilt incident table), and/or
- one Chrome-trace JSON (``--chrome-out``) in the exact event shape
  :class:`~dlrover_tpu.utils.tracing.Tracer` exports, so a single
  Perfetto view spans master + agents + workers.

Usage::

    python -m dlrover_tpu.cli timeline --state-dir /tmp/job-state
    python -m dlrover_tpu.cli timeline --goodput-json goodput.json \
        --trace /tmp/agent-trace.json \
        --trace /tmp/agent-trace.worker0.0.jsonl --chrome-out merged.json
"""

import argparse
import json
import os
import sys
import time
from typing import List, Optional

from dlrover_tpu.common import env_utils
from dlrover_tpu.observability.events import JobEvent
from dlrover_tpu.observability.goodput import GoodputLedger
from dlrover_tpu.utils.tracing import read_events


def load_events_from_state_dir(state_dir: str) -> List[JobEvent]:
    """Recover the durable event stream: snapshot events, then journaled
    ``("event", ...)`` records and ``EventReport`` RPC records (which are
    exactly the post-snapshot additions — the generation chain guarantees
    no overlap)."""
    from dlrover_tpu.common import messages as m
    from dlrover_tpu.master.state_store import MasterStateStore

    store = MasterStateStore(state_dir)
    state, records = store.recover()
    events: List[JobEvent] = []
    if state:
        for d in state.get("events", {}).get("events", ()):
            events.append(JobEvent.from_dict(d))
    for rec in records:
        try:
            if rec[0] == "event":
                events.append(rec[1])
            elif rec[0] == "rpc" and isinstance(rec[2], m.EventReport):
                events.extend(rec[2].events)
        except Exception:  # dtlint: disable=DT001 -- replaying a possibly-corrupt journal: skip the bad record, keep the timeline
            continue
    return events


def load_events_from_dump(path: str) -> List[JobEvent]:
    with open(path) as f:
        dump = json.load(f)
    return [JobEvent.from_dict(d) for d in dump.get("events", ())]


def merge_events(*sources: List[JobEvent]) -> List[JobEvent]:
    merged: List[JobEvent] = []
    for src in sources:
        merged.extend(src)
    merged.sort(key=lambda e: (e.ts, e.seq))
    return merged


def _fmt_args(args: dict, width: int = 100) -> str:
    body = " ".join(f"{k}={v}" for k, v in args.items())
    return body if len(body) <= width else body[: width - 1] + "…"


def render_text(events: List[JobEvent], out=None) -> None:
    out = out or sys.stdout
    if not events:
        print("no events", file=out)
        return
    t0 = events[0].ts
    print(f"== job timeline: {len(events)} events, "
          f"{events[-1].ts - t0:.1f}s ==", file=out)
    for ev in events:
        who = f"{ev.role or '?'} n{ev.node_id}" if ev.node_id >= 0 else (
            ev.role or "master"
        )
        clock = time.strftime("%H:%M:%S", time.localtime(ev.ts))
        print(
            f"{clock} +{ev.ts - t0:9.3f}s  [{who:<10}] "
            f"{ev.kind:<26} {_fmt_args(ev.args)}",
            file=out,
        )
    # Rebuild the incident view from the stream (step reports are not
    # events, so incidents without a later fault stay open here — the
    # authoritative numbers live in the master's goodput summary).
    ledger = GoodputLedger(now=t0)
    for ev in events:
        ledger.ingest(ev)
    summary = ledger.summary(now=events[-1].ts)
    if summary["incidents"]:
        print("\n== incidents ==", file=out)
        for inc in summary["incidents"]:
            state = "open" if inc["open"] else f"{inc['recover_s']:.1f}s"
            detect = (
                "-" if inc["detect_s"] is None
                else f"{inc['detect_s']:.1f}s"
            )
            # Remediation incidents carry a third stamp: when the
            # policy's quarantine actually moved the world.
            act = (
                "" if inc.get("act_s") is None
                else f"  act={inc['act_s']:.1f}s"
            )
            print(
                f"  +{inc['start_ts'] - t0:9.3f}s  node {inc['node_id']} "
                f" cause={inc['cause']}  detect={detect}{act}"
                f"  recover={state}"
                f"{'  [injected]' if inc['injected'] else ''}",
                file=out,
            )
            # Straggler incidents carry the detector's phase/probe
            # evidence (which key degraded, by how much vs baseline);
            # rescale incidents carry the reshape's spec diff and
            # d2d/snapshot byte split (or the decline reason);
            # remediation incidents carry the quarantine plan and the
            # old->new world.
            if inc.get("evidence"):
                print(f"             evidence: {inc['evidence']}", file=out)


def to_chrome_trace(events: List[JobEvent]) -> List[dict]:
    """JobEvents as Tracer-shaped instant events (merge-compatible)."""
    out = []
    for ev in events:
        out.append({
            "name": ev.kind, "ph": "i", "s": "p",
            "pid": ev.pid or 0, "tid": 0, "ts": ev.ts * 1e6,
            "args": {
                **ev.args, "node_id": ev.node_id, "role": ev.role,
                "seq": ev.seq,
            },
        })
    return out


def write_chrome_trace(events: List[JobEvent], trace_files: List[str],
                       out_path: str) -> int:
    merged = to_chrome_trace(events)
    for path in trace_files:
        try:
            merged.extend(read_events(path))
        except Exception as e:
            print(f"skipping unreadable trace {path}: {e}",
                  file=sys.stderr)
    merged.sort(key=lambda e: e.get("ts", 0))
    with open(out_path, "w") as f:
        json.dump({"traceEvents": merged}, f)
    return len(merged)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "dlrover-tpu timeline",
        description="render the merged job event log",
    )
    p.add_argument("--state-dir", default="",
                   help="master --state_dir to recover the event log from")
    p.add_argument("--goodput-json", default="",
                   help="a goodput artifact (ObservabilityPlane dump)")
    p.add_argument("--trace", action="append", default=[],
                   help="trace file to merge: the agent's Chrome trace JSON "
                   "or a worker's .jsonl (repeatable)")
    p.add_argument("--chrome-out", default="",
                   help="write the merged Chrome trace JSON here")
    p.add_argument("--no-text", action="store_true",
                   help="skip the human-readable rendering")
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if not args.state_dir and not args.goodput_json:
        print("need --state-dir and/or --goodput-json", file=sys.stderr)
        return 2
    sources = []
    if args.state_dir:
        sources.append(load_events_from_state_dir(args.state_dir))
    if args.goodput_json:
        sources.append(load_events_from_dump(args.goodput_json))
    events = merge_events(*sources)
    lockdep_path = env_utils.LOCKDEP_EXPORT.get()
    if lockdep_path and os.path.exists(lockdep_path):
        # The master wrote its lock-order graph at stop; point the
        # operator (and dtlint --lockdep-graph) at it.
        print(f"lockdep graph artifact: {lockdep_path}", file=sys.stderr)
    if not args.no_text:
        render_text(events)
    if args.chrome_out:
        n = write_chrome_trace(events, args.trace, args.chrome_out)
        print(f"wrote {n} trace events to {args.chrome_out}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
