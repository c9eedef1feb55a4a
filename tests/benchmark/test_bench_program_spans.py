"""The readers of the program's own spans: each new per-layer metric
against a span file small enough to compute by hand, nothing (not 0) where
the program left no spans, the idle attribution on a made-up trace, and
the twelve entries of ``BENCHMARK.json``."""

import json
import os
import shutil
import types

import pytest

from benchmark import cells, program_spans, xplane
from benchmark import run as bench_run

ROOT = cells.ROOT
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
S = 1e6     # a second, in the files' microseconds

STEADY = ["gpt2-xl.steady", "mistral-7b.long16k", "gpt2-xl.fsdp4"]
ELASTIC = ["gpt2-xl.elastic"]
TABLE = {
    "trainer.dispatch_ms": ("ms", "program_span", "trainer", STEADY),
    "trainer.host_ms": ("ms", "program_span", "trainer", STEADY),
    "trainer.report_ms": ("ms", "program_span", "trainer", STEADY),
    "trainer.untraced_share": ("%", "program_span", "trainer", STEADY),
    "trainer.device_put_s": ("s", "program_span", "trainer", ELASTIC),
    "trainer.host_next_s": ("s", "program_span", "trainer", ELASTIC),
    "ckpt.own_copies_s": ("s", "program_span", "checkpoint", ELASTIC),
    "ckpt.fetch_s": ("s", "program_span", "checkpoint", ELASTIC),
    "ckpt.shm_copy_s": ("s", "program_span", "checkpoint", ELASTIC),
    "ckpt.snapshots_skipped": ("snapshots", "program_counter", "checkpoint",
                               ELASTIC),
    "ckpt.idle_in_device_put_s": ("s", "device_trace", "checkpoint", ELASTIC),
    "ckpt.idle_unnamed_s": ("s", "device_trace", "checkpoint", ELASTIC),
}


class _File:
    """Builds a worker's span file the way the tracer writes it."""

    def __init__(self):
        self.events, self._id = [], 0

    def span(self, name, ts_s, dur_s, parent=None, **args):
        self._id += 1
        args["id"] = self._id
        if parent is not None:
            args["parent"] = parent
        self.events.append({"name": name, "ph": "X", "pid": 1, "tid": 1,
                            "ts": ts_s * S, "dur": dur_s * S, "args": args})
        return self._id

    def count(self, name, ts_s, **series):
        self.events.append({"name": name, "ph": "C", "pid": 1, "tid": 1,
                            "ts": ts_s * S, "args": series})

    def write(self, out, name="agent_trace.worker0.0.jsonl", cut=""):
        with open(os.path.join(out, name), "w") as f:
            for e in self.events:
                f.write(json.dumps(e) + "\n")
            f.write(cut)


def steady_file() -> _File:
    """Steps 1..7, step k: 0.5 s + k ms long, of which the fence waits
    0.49 s; dispatch 2 ms + 0.1 k ms; input 1 ms (0.3 + 0.5 inside);
    report 0.4 ms; readback 0.1 ms; callbacks 0.05 ms."""
    f = _File()
    f.span("input.host_next", 1000.0, 0.0003)       # the first fill
    f.span("input.device_put", 1000.1, 0.0005, bytes=32768)
    for k in range(1, 8):
        t = 1008.0 + 0.5 * k
        step = f.span("trainer.step", t, 0.5 + 0.001 * k, step=k)
        inp = f.span("trainer.input", t, 0.001, step, step=k)
        f.span("input.host_next", t, 0.0003, inp, step=k)
        f.span("input.device_put", t + 0.0004, 0.0005, inp, step=k)
        f.span("trainer.dispatch", t + 0.001, 0.002 + 0.0001 * k, step, step=k)
        f.span("trainer.report", t + 0.004, 0.0004, step, step=k)
        f.span("trainer.fence", t + 0.005, 0.49, step, step=k)
        f.span("trainer.readback", t + 0.496, 0.0001, step, step=k)
        f.span("trainer.callbacks", t + 0.497, 0.00005, step, step=k)
    return f


def steady_flush() -> dict:
    """The window holds the ends of steps 3..6 (as the harness' tests'
    ``_flush``: a stamp in ``on_step_end(n)`` is the end of step n - 1)."""
    stamps = [[n, 8.5 + 0.5 * n, 1008.5 + 0.5 * n, 5.0] for n in range(1, 8)]
    return {"event": "done", "incarnation": 0, "stamps": stamps,
            "t_open": 10.0, "t_close": 12.0, "t_open_wall": 1010.0,
            "t_close_wall": 1012.0, "dispatched": [], "landed": []}


def elastic_file() -> _File:
    """Two snapshots land in the window (1010, 1040]: of step 1, taken at
    1001.0 and restorable at 1020.0, and of step 20, 1020.5 -> 1040.0; a
    third, of step 45, is in flight at the kill."""
    f = _File()
    for step, t_take, own, fetch, copy, t_land in (
        (1, 1001.0, 1.2, 12.0, 5.0, 1020.0),
        (20, 1020.5, 2.0, 14.0, 4.0, 1040.0),
        (45, 1040.6, 1.0, None, None, None),
    ):
        save = f.span("trainer.save", t_take - 0.01, own + 0.32, step=step)
        snap = f.span("ckpt.snapshot", t_take, own + 0.3, save, step=step)
        f.span("ckpt.own_copies", t_take + 0.2, own, snap, step=step,
               bytes=6_300_000_000)
        if t_land is None:
            continue
        stage = f.span("ckpt.stage", t_take + own + 0.3,
                       t_land - t_take - own - 0.3, step=step)
        f.span("ckpt.fetch", t_take + own + 0.3, fetch, stage, step=step,
               bytes=6_300_000_000, chunks=190)
        f.span("ckpt.lock_wait", t_land - copy - 0.3, 0.001, stage, step=step)
        f.span("ckpt.shm_copy", t_land - copy - 0.2, copy, stage, step=step)
        f.span("ckpt.shm_flush", t_land - 0.2, 0.1, stage, step=step)
        f.span("ckpt.publish", t_land - 0.1, 0.1, stage, step=step)
    for ts, dur in ((1002.0, 6.0), (1010.0, 0.001), (1020.2, 0.5),
                    (1021.0, 9.0), (1035.0, 0.003)):
        f.span("input.device_put", ts, dur, step=2)
    for ts, dur in ((1001.9, 0.0004), (1020.9, 0.0006), (1040.2, 0.3)):
        f.span("input.host_next", ts, dur, step=2)
    total = {}
    for ts, reason in (
        (1003, "staging_in_flight"), (1005, "staging_in_flight"),
        (1007, "staging_in_flight"),                 # 3 in the first cycle
        (1020.2, "staging_in_flight"),               # between the two
        (1022, "staging_in_flight"), (1024, "governor"),
        (1026, "staging_in_flight"), (1028, "staging_in_flight"),
        (1030, "staging_in_flight"),                 # 5 in the second
        (1041, "staging_in_flight"),                 # after the window
    ):
        total["reason=" + reason] = total.get("reason=" + reason, 0) + 1
        f.count("ckpt.skipped", ts, **total)
    return f


def elastic_flush() -> dict:
    return {"event": "kill", "incarnation": 0, "stamps": [],
            "t_open": 10.0, "t_close": 40.0, "t_open_wall": 1010.0,
            "t_close_wall": 1040.0, "tokens_per_step": 4096,
            "dispatched": [[1, 1002.6], [20, 1022.9], [45, 1041.9]],
            "landed": [[1, 1020.003], [20, 1040.0]]}


IDLE = {"window_s": 14.0, "idle_s": 11.0, "attributed_s": 10.9,
        "unnamed_s": 0.25,
        "by_span_s": {"input.device_put": 9.5, "ckpt.own_copies": 1.15}}

# What each reader must give on those files, computed by hand.
EXPECTED = {
    # dispatch of steps 3..6: 2.3, 2.4, 2.5, 2.6 ms
    "trainer.dispatch_ms": 2.45,
    # step less fence, steps 3..6: 13, 14, 15, 16 ms
    "trainer.host_ms": 14.5,
    "trainer.report_ms": 0.4,
    # self time of step k: (500 + k) - (1 + 2 + 0.1 k + 0.4 + 490 + 0.1
    # + 0.05) = 6.45 + 0.9 k ms; steps 3..6: 42 ms of 2018 ms
    "trainer.untraced_share": 100 * 42.0 / 2018.0,
    # cycles (1001.0, 1020.0) and (1020.5, 1040.0): 6.001 and 9.003 s
    "trainer.device_put_s": 7.502,
    "trainer.host_next_s": 0.0005,
    "ckpt.own_copies_s": 1.6,
    "ckpt.fetch_s": 13.0,
    "ckpt.shm_copy_s": 4.5,
    "ckpt.snapshots_skipped": 4.0,      # 3 and 5
    "ckpt.idle_in_device_put_s": 9.5,
    "ckpt.idle_unnamed_s": 0.25,
}


def _context(tmp_path, name, spans=True, cut=""):
    """The harness' own context over a run's directory holding (or not)
    the worker's span file and the kept idle reduction."""
    elastic = TABLE[name][3] == ELASTIC
    cell = cells.resolve(TABLE[name][3][0], ROOT, rehearsal=True)
    out = str(tmp_path)
    cell.update(out=out, seed=0, seconds=2, trace=1)
    if spans:
        (elastic_file() if elastic else steady_file()).write(out, cut=cut)
        if TABLE[name][1] == "device_trace":
            os.mkdir(os.path.join(out, "trace"))
            with open(os.path.join(out, "trace_program_spans.json"), "w") as f:
                json.dump(IDLE, f)
    flush = elastic_flush() if elastic else steady_flush()
    return cell, bench_run.Context(cell, [flush], out, None, "cpu")


class TestReaders:
    @pytest.mark.parametrize("name", sorted(TABLE))
    def test_a_reader_gives_the_hand_computed_value(self, tmp_path, name):
        cell, ctx = _context(tmp_path, name)
        got = bench_run.per_layer(cell, ctx)
        assert got[name]["unit"] == TABLE[name][0]
        assert got[name]["value"] == pytest.approx(EXPECTED[name], rel=1e-9)

    @pytest.mark.parametrize("name", sorted(TABLE))
    def test_without_the_programs_spans_a_reader_gives_nothing(
        self, tmp_path, name
    ):
        """As on a program from before the spans: the metric is left out
        of the line, not reported as 0."""
        cell, ctx = _context(tmp_path, name, spans=False)
        path = os.path.join(cell["bench_dir"], "layer_metrics", name + ".py")
        assert cells.load_module(path).read(ctx) is None
        assert name not in bench_run.per_layer(cell, ctx)

    def test_the_line_a_kill_cut_short_is_dropped(self, tmp_path):
        cell, ctx = _context(tmp_path, "ckpt.fetch_s",
                             cut='{"name": "ckpt.fetch", "ph": "X", "ts": 1')
        assert len(program_spans.events(ctx)) == len(elastic_file().events)
        assert bench_run.per_layer(cell, ctx)["ckpt.fetch_s"]["value"] == 13.0

    def test_other_incarnations_and_ranks_are_not_mixed_in(self, tmp_path):
        cell, ctx = _context(tmp_path, "ckpt.fetch_s")
        other = _File()
        other.span("ckpt.fetch", 1015.0, 99.0, step=1)
        other.write(str(tmp_path), "agent_trace.worker0.1.jsonl")
        other.write(str(tmp_path), "agent_trace.worker1.0.jsonl")
        assert bench_run.per_layer(cell, ctx)["ckpt.fetch_s"]["value"] == 13.0
        assert [e["dur"] for e in program_spans.events(ctx, incarnation=1)] == [
            99.0 * S
        ]


# --------------------------------------------------------- idle by span

def _event(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=end - start, stats=[])


def _line(name, *events):
    return types.SimpleNamespace(name=name, events=list(events))


def made_up_profile():
    """Three runs of the step program; the window is the last two (1000 ..
    2100 ns). The device idles 1100-1500, 1520-2000 and 2010-2090."""
    device = types.SimpleNamespace(name="/device:TPU:0", lines=[
        _line("XLA Modules", _event("jit_step", 0, 100),
              _event("jit_step", 1000, 1100), _event("jit_step", 2000, 2100)),
        _line("XLA Ops", _event("%a = f32[] add()", 0, 100),
              _event("%a = f32[] add()", 1000, 1100),
              _event("%copy = f32[] copy()", 1500, 1520),
              _event("%a = f32[] add()", 2000, 2010),
              _event("%b = f32[] add()", 2090, 2100)),
    ])
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        _line("python3", _event("ckpt.stage", 1000, 2000),       # staging
              _event("ckpt.fetch", 1000, 1900)),
        _line("python3", _event("trainer.step", 900, 2200),      # the loop
              _event("trainer.input", 1090, 1600),
              _event("input.host_next", 1095, 1100),
              _event("input.device_put", 1100, 1590),
              _event("trainer.dispatch", 1600, 1700),
              _event("bench.next_batch", 1080, 1610),
              _event("trainer.step", 2200, 2300)),
    ])
    return types.SimpleNamespace(planes=[host, device])


class TestIdleBySpan:
    def test_leaves_are_the_spans_with_none_inside(self):
        spans = [(0, 100, "trainer.step"), (10, 50, "trainer.input"),
                 (10, 20, "input.host_next"), (20, 50, "input.device_put"),
                 (50, 60, "trainer.dispatch"), (100, 110, "trainer.step")]
        assert [s[2] for s in program_spans.leaves(spans)] == [
            "input.host_next", "input.device_put", "trainer.dispatch",
            "trainer.step",
        ]

    def test_idle_time_is_laid_against_the_loop_threads_leaf_spans(
        self, monkeypatch
    ):
        monkeypatch.setattr(xplane, "load", lambda path: made_up_profile())
        got = program_spans.reduce_idle("anywhere")
        assert got["window_s"] == pytest.approx(1100e-9)
        assert got["idle_s"] == pytest.approx(960e-9)
        assert got["attributed_s"] == pytest.approx(got["idle_s"])
        # 1100-1500 lies in input.device_put; of 1520-2000 it is open for
        # 70 ns and trainer.dispatch for 100, no leaf for the other 310;
        # 2010-2090 has only trainer.step open, which is no leaf. The
        # staging thread's spans and the benchmark's own name nothing.
        assert got["by_span_s"] == {
            "input.device_put": pytest.approx(470e-9),
            "trainer.dispatch": pytest.approx(100e-9),
        }
        assert got["unnamed_s"] == pytest.approx(390e-9)
        # A whole gap to the span open for most of it, as idle_gaps does:
        # 1520-2000 goes to trainer.dispatch whole.
        assert got["by_longest_s"] == {
            "input.device_put": pytest.approx(400e-9),
            "trainer.dispatch": pytest.approx(480e-9),
            "host: no span": pytest.approx(80e-9),
        }

    def test_a_trace_without_the_spans_reduces_to_nothing(self, tmp_path):
        """The recorded v5e trace is of a program from before the spans:
        the reduction runs in its own process, finds none, and the readers
        give nothing; the (empty) result is kept beside the trace."""
        os.mkdir(tmp_path / "trace")
        shutil.copy(os.path.join(DATA, "toy_gpt2_one_chip.xplane.pb.gz"),
                    tmp_path / "trace")
        ctx = types.SimpleNamespace(cell={"out": str(tmp_path)})
        assert program_spans.idle(ctx) is None
        assert program_spans.idle_seconds(ctx, "input.device_put") is None
        with open(tmp_path / "trace_program_spans.json") as f:
            assert json.load(f) == {}

    def test_a_run_without_a_trace_has_no_idle_reduction(self, tmp_path):
        ctx = types.SimpleNamespace(cell={"out": str(tmp_path)})
        assert program_spans.idle(ctx) is None
        assert os.listdir(tmp_path) == []


# ------------------------------------------------------- BENCHMARK.json

class TestTheTwelveEntries:
    def test_each_lists_exactly_its_cells(self):
        bench = cells.load_benchmark(ROOT)
        entries = {m["name"]: m for m in bench["per_layer"]}
        moved = {"tokens_per_s": STEADY, "staging_tokens_per_s": ELASTIC}
        for name, (unit, source, layer, where) in TABLE.items():
            m = entries[name]
            assert (m["unit"], m["source"], m["layer"]) == (unit, source, layer)
            assert m["better"] == "lower"
            assert m["workloads"] == where == moved[m["moves"]]
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "layer_metrics", name + ".py"
            ))
        # Appended: what was there keeps its place.
        assert [m["name"] for m in bench["per_layer"]][-12:] == list(TABLE)

    def test_a_cell_reports_the_ones_of_its_row(self):
        for cell_name in STEADY + ELASTIC:
            listed = {m["name"] for m in
                      cells.resolve(cell_name, ROOT)["per_layer"]}
            for name, (_, _, _, where) in TABLE.items():
                assert (name in listed) == (cell_name in where)
