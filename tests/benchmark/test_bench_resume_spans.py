"""The readers of the resume path's spans: each of the ten metrics against
an agent ring, a restarted worker's span file and records small enough to
compute by hand, nothing (not 0) from a program without those spans, and
their ten entries in ``BENCHMARK.json``."""

import json
import os

import pytest

from benchmark import cells, resume_path
from benchmark import run as bench_run

ROOT = cells.ROOT
S = 1e6     # a second, in the files' microseconds
ELASTIC = ["gpt2-xl.elastic"]

TABLE = {   # name: (layer, moves)
    "agent.exit_seen_s": ("agent", "staging_tokens_per_s"),
    "agent.report_failure_s": ("agent", "staging_tokens_per_s"),
    "ckpt.flush_lock_wait_s": ("agent", "staging_tokens_per_s"),
    "ckpt.flush_persist_s": ("agent", "staging_tokens_per_s"),
    "ckpt.flush_commit_s": ("agent", "staging_tokens_per_s"),
    "agent.restart_s": ("agent", "staging_tokens_per_s"),
    "agent.device_check_s": ("agent", "setup_s"),
    "ckpt.restore_copy_s": ("checkpoint", "staging_tokens_per_s"),
    "trainer.restore_put_s": ("trainer", "staging_tokens_per_s"),
    "agent.resume_unnamed_s": ("agent", "staging_tokens_per_s"),
}


class _Spans:
    """Spans of one process, as its tracer records them."""

    def __init__(self, pid):
        self.events, self._id, self._pid = [], 0, pid

    def span(self, name, ts_s, dur_s, parent=None, tid=1, **args):
        self._id += 1
        args["id"] = self._id
        if parent is not None:
            args["parent"] = parent
        self.events.append({"name": name, "ph": "X", "pid": self._pid,
                            "tid": tid, "ts": ts_s * S, "dur": dur_s * S,
                            "args": args})
        return self._id


def agent_ring(full=True) -> _Spans:
    """The SIGKILL at 1000.0; the poll sees it at 1004.0. A persist of the
    per-step path at 950 lies under no flush. Without ``full``, what a
    program from before these spans exports."""
    a = _Spans(pid=7)
    if full:
        a.span("agent.device_check", 900.0, 18.0)
        a.span("ckpt.flush_lock_wait", 950.0, 5.0, tid=2, lock="shard")
        a.span("ckpt.persist_shard", 955.0, 7.0, tid=2)
        failure = a.span("agent.failure", 1004.0, 25.0, cause="crash")
        a.span("agent.report_failure", 1004.0, 0.05, failure)
    flush = a.span("ckpt-crash-flush", 1004.05, 24.9,
                   failure if full else None)
    if full:
        a.span("ckpt.flush_scan", 1004.05, 0.01, flush)
        a.span("ckpt.flush_lock_wait", 1004.06, 0.02, flush, lock="flush")
        a.span("ckpt.flush_lock_wait", 1004.08, 0.03, flush, lock="shard")
        persist = a.span("ckpt.persist_shard", 1004.11, 24.5, flush)
        a.span("ckpt.persist_write", 1004.2, 24.4, persist)
        commit = a.span("ckpt.flush_commit", 1028.61, 0.3, flush)
        a.span("ckpt.gc", 1028.8, 0.1, commit)
        restart = a.span("agent.restart", 1029.1, 0.2, reason="failed")
        a.span("agent.stop_workers", 1029.1, 0.01, restart)
        a.span("rendezvous", 1029.12, 0.02, restart)
        a.span("agent.start_workers", 1029.2, 0.1, restart)
    else:
        a.span("rendezvous", 1029.12, 0.02)
    return a


def worker_file(full=True) -> _Spans:
    """The restarted worker: its restore from 1042.5 to 1050.0, then 3 s
    in no span (the benchmark's fingerprint), the first fill and the
    first step; a staging span on another thread over that gap."""
    w = _Spans(pid=9)
    if full:
        outer = w.span("trainer.restore", 1042.5, 7.5, step=20)
        inner = w.span("ckpt.restore", 1042.5, 6.9, outer, source="memory")
        for name, ts, dur in (
            ("ckpt.restore_wait_staged", 1042.5, 0.001),
            ("ckpt.restore_locate", 1042.501, 0.05),
            ("ckpt.restore_copy", 1042.551, 0.2),
            ("ckpt.restore_read", 1042.751, 0.01),
            ("ckpt.restore_copy", 1042.761, 6.6),
            ("ckpt.restore_device_put", 1049.361, 0.0),
        ):
            w.span(name, ts, dur, inner)
        w.span("trainer.restore_put", 1049.4, 0.6, outer)
    w.span("ckpt.stage", 1050.0, 3.0, tid=2, step=20)
    w.span("input.host_next", 1053.0, 0.01)
    w.span("input.device_put", 1053.01, 0.05)
    step = w.span("trainer.step", 1053.1, 3.0, step=20)
    w.span("trainer.fence", 1053.2, 2.8, step)
    return w


def records() -> list:
    kill = {"event": "kill", "incarnation": 0, "stamps": [],
            "t_open": 10.0, "t_close": 40.0, "t_open_wall": 1010.0,
            "t_close_wall": 1040.0, "dispatched": [], "landed": [],
            "t_kill": 1000.0}
    return [
        kill,
        {"event": "start", "incarnation": 1, "t": 1029.5,
         "t_entry": 1029.4, "spawn_s": 0.05},
        {"event": "built", "incarnation": 1, "t": 1042.4},
        {"event": "resume", "incarnation": 1, "t": 1053.0, "step": 20,
         "restore_s": 7.5, "restore": None},
        {"event": "first_step", "incarnation": 1, "t": 1056.0,
         "t_done": 1056.0, "step": 21},
    ]


# What each reader gives on those files, by hand.
EXPECTED = {
    "agent.exit_seen_s": 4.0,
    "agent.report_failure_s": 0.05,
    "ckpt.flush_lock_wait_s": 0.05,         # not the per-step 5.0
    "ckpt.flush_persist_s": 24.5,           # not the per-step 7.0
    "ckpt.flush_commit_s": 0.3,
    "agent.restart_s": 0.2,
    "agent.device_check_s": 18.0,
    "ckpt.restore_copy_s": 6.8,
    "trainer.restore_put_s": 0.6,
    # [1000, 1056] less [1000, 1004], [1004, 1029], [1029.1, 1029.3],
    # [1029.4, 1042.4] and the loop thread's outermost spans [1042.5,
    # 1050], [1053, 1053.06], [1053.1, 1056]: 0.1 + 0.1 + 0.1 + 3 + 0.04
    "agent.resume_unnamed_s": 3.34,
}


def _context(tmp_path, full=True, recs=None):
    cell = cells.resolve("gpt2-xl.elastic", ROOT, rehearsal=True)
    out = str(tmp_path)
    cell.update(out=out, seed=0, seconds=2, trace=1)
    with open(os.path.join(out, "agent_trace.json"), "w") as f:
        json.dump({"traceEvents": agent_ring(full).events}, f)
    for incarnation, spans in ((0, worker_file(False)),
                               (1, worker_file(full))):
        name = f"agent_trace.worker0.{incarnation}.jsonl"
        with open(os.path.join(out, name), "w") as f:
            for e in spans.events:
                f.write(json.dumps(e) + "\n")
    recs = records() if recs is None else recs
    return cell, bench_run.Context(cell, recs, out, None, "cpu")


class TestReaders:
    @pytest.mark.parametrize("name", sorted(TABLE))
    def test_a_reader_gives_the_hand_computed_value(self, tmp_path, name):
        cell, ctx = _context(tmp_path)
        got = bench_run.per_layer(cell, ctx)
        assert got[name]["unit"] == "s"
        assert got[name]["value"] == pytest.approx(EXPECTED[name], abs=1e-6)

    @pytest.mark.parametrize("name", sorted(TABLE))
    def test_without_the_programs_spans_a_reader_gives_nothing(
        self, tmp_path, name
    ):
        cell, ctx = _context(tmp_path, full=False)
        assert name not in bench_run.per_layer(cell, ctx)

    def test_a_run_that_never_resumed_has_no_unnamed_seconds(self, tmp_path):
        cell, ctx = _context(tmp_path, recs=records()[:1])
        assert resume_path.unnamed_s(ctx) is None

    def test_the_restore_a_first_incarnation_made_is_not_read(self, tmp_path):
        """Incarnation 0 restores nothing on the chip but has the spans;
        only the restarted worker's count."""
        cell, ctx = _context(tmp_path)
        name = "agent_trace.worker0.0.jsonl"
        with open(os.path.join(ctx.cell["out"], name), "w") as f:
            for e in worker_file(True).events:
                e = dict(e, args=dict(e["args"]))
                if e["name"] == "ckpt.restore_copy":
                    e["dur"] *= 10
                f.write(json.dumps(e) + "\n")
        assert bench_run.per_layer(cell, ctx)["ckpt.restore_copy_s"][
            "value"] == pytest.approx(6.8)


class TestUnion:
    @pytest.mark.parametrize("intervals, covered", [
        ([], 0.0),
        ([(1.0, 2.0), (1.5, 3.0)], 2.0),           # overlapping
        ([(0.0, 1.0), (2.0, 3.0)], 1.5),           # the window cuts one
        ([(1.0, 4.0), (2.0, 3.0)], 3.0),           # one inside another
        ([(-5.0, -1.0), (9.0, 12.0)], 0.0),        # outside the window
    ])
    def test_seconds_of_a_window_the_intervals_cover(self, intervals,
                                                     covered):
        assert resume_path.union_s(intervals, 0.5, 4.0) == pytest.approx(
            covered)


class TestTheTenEntries:
    def test_they_follow_each_other_each_with_its_cell(self):
        """Appended together, in this order, each in the elastic cell
        alone; nothing is said of what may follow them."""
        bench = cells.load_benchmark(ROOT)
        names = [m["name"] for m in bench["per_layer"]]
        first = names.index("agent.exit_seen_s")
        assert names[first:first + len(TABLE)] == list(TABLE)
        entries = {m["name"]: m for m in bench["per_layer"]}
        for name, (layer, moves) in TABLE.items():
            m = entries[name]
            assert (m["unit"], m["better"], m["source"]) == (
                "s", "lower", "program_span")
            assert (m["layer"], m["moves"]) == (layer, moves)
            assert m["workloads"][:1] == ELASTIC
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "layer_metrics", name + ".py"))

    def test_only_the_elastic_cell_reports_them(self):
        for cell_name in ("gpt2-xl.steady", "gpt2-xl.elastic"):
            listed = {m["name"] for m in
                      cells.resolve(cell_name, ROOT)["per_layer"]}
            assert (set(TABLE) <= listed) == (cell_name in ELASTIC)
            assert not (set(TABLE) & listed) or cell_name in ELASTIC
