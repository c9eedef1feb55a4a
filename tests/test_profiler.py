"""Profiler + tracing tests (SURVEY §2.5 profiler, §5 tracing)."""

import glob
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.utils import tracing
from dlrover_tpu.utils.profiler import Profiler, device_peak_flops
from dlrover_tpu.utils.tracing import SPANS, Tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestProfiler:
    def test_step_and_phase_stats(self):
        prof = Profiler()
        for _ in range(5):
            with prof.step():
                with prof.phase("data"):
                    time.sleep(0.01)
                with prof.phase("compute"):
                    time.sleep(0.02)
        rep = prof.report()
        assert rep["steps"] == 5
        assert rep["step_time_mean_s"] >= 0.03
        assert rep["phases"]["data"]["mean_s"] >= 0.01
        assert rep["phases"]["compute"]["share"] > rep["phases"]["data"]["share"]

    def test_cost_analysis_flops(self):
        """Compiler-reported flops for a matmul must match 2*M*N*K."""
        prof = Profiler()
        m = 256

        @jax.jit
        def f(a, b):
            return a @ b

        a = jnp.ones((m, m), jnp.float32)
        cost = prof.analyze(f, a, a)
        assert cost["flops"] == pytest.approx(2 * m ** 3, rel=0.01)

    def test_utilization_needs_data(self):
        prof = Profiler()
        assert prof.utilization() == -1.0

    def test_mfu_computation(self):
        prof = Profiler()
        prof._cost = {"flops": 1e9, "bytes_accessed": 0}
        with prof.step():
            time.sleep(0.01)
        # On CPU device_peak_flops is 0 -> -1; force a peak.
        mfu = prof.utilization(device=None) if device_peak_flops() else None
        u = prof._cost["flops"] / prof._step_stats.mean / 1e12
        assert u > 0  # arithmetic sanity

    def test_trace_capture_writes_events(self, tmp_path):
        """jax.profiler trace capture on the step schedule produces
        profile artifacts."""
        import os

        prof = Profiler(trace_dir=str(tmp_path), trace_steps=(1, 2))

        @jax.jit
        def f(x):
            return x * 2

        x = jnp.ones(8)
        for _ in range(4):
            with prof.step():
                jax.block_until_ready(f(x))
        found = []
        for root, _, files in os.walk(tmp_path):
            found.extend(files)
        assert found, "no trace artifacts written"


class TestTracer:
    def test_span_and_instant(self):
        tracer = Tracer()
        with tracer.span("rendezvous", round=1):
            time.sleep(0.005)
        tracer.instant("crash", rank=2)
        events = tracer.events
        assert len(events) == 2
        span = next(e for e in events if e["ph"] == "X")
        assert span["name"] == "rendezvous"
        assert span["dur"] >= 5000  # microseconds
        assert span["args"]["round"] == 1

    def test_export_chrome_trace(self, tmp_path):
        tracer = Tracer()
        tracer.instant("e1")
        tracer.count("mem", 512)
        path = str(tmp_path / "trace.json")
        tracer.export(path)
        with open(path) as f:
            doc = json.load(f)
        assert len(doc["traceEvents"]) == 2

    def test_export_without_path_is_noop(self, monkeypatch):
        monkeypatch.delenv("DLROVER_TPU_TRACE_FILE", raising=False)
        tracer = Tracer()
        tracer.instant("e")
        assert tracer.export() is None

    def test_capacity_bounded(self):
        tracer = Tracer(capacity=10)
        for i in range(100):
            tracer.instant(f"e{i}")
        assert len(tracer.events) == 10

    def test_nesting_gives_parent_and_step(self):
        tracer = Tracer()
        with tracer.span("trainer.step", step=7) as outer:
            with tracer.span("trainer.input") as inner:
                with tracer.span("input.host_next"):
                    pass
            with tracer.span("trainer.save", step=8):
                pass
        with tracer.span("alone"):
            pass
        by_name = {e["name"]: e["args"] for e in tracer.events}
        assert "parent" not in by_name["trainer.step"]
        assert by_name["trainer.input"]["parent"] == outer.args["id"]
        assert by_name["input.host_next"]["parent"] == inner.args["id"]
        # A span without a step of its own takes its parent's.
        assert by_name["trainer.input"]["step"] == 7
        assert by_name["input.host_next"]["step"] == 7
        assert by_name["trainer.save"]["step"] == 8
        assert "step" not in by_name["alone"]
        assert "parent" not in by_name["alone"]
        ids = [e["args"]["id"] for e in tracer.events]
        assert len(set(ids)) == len(ids)

    def test_a_thread_has_its_own_stack(self):
        tracer = Tracer()

        def stage():
            with tracer.span("ckpt.stage", step=3):
                with tracer.span("ckpt.fetch"):
                    pass

        with tracer.span("trainer.step", step=4):
            worker = threading.Thread(target=stage)
            worker.start()
            worker.join()
        by_name = {e["name"]: e for e in tracer.events}
        assert "parent" not in by_name["ckpt.stage"]["args"]
        assert by_name["ckpt.fetch"]["args"]["step"] == 3
        assert by_name["ckpt.stage"]["tid"] != by_name["trainer.step"]["tid"]

    def test_a_span_raised_through_still_closes_and_records(self):
        tracer = Tracer()
        with pytest.raises(StopIteration):
            with tracer.span("trainer.step", step=1):
                with tracer.span("input.host_next") as inner:
                    raise StopIteration
        assert [e["name"] for e in tracer.events] == [
            "input.host_next", "trainer.step"
        ]
        assert inner.duration_s >= 0.0
        with tracer.span("after"):      # the stack was unwound
            pass
        assert "parent" not in tracer.events[-1]["args"]

    def test_the_closed_span_exposes_its_duration(self):
        tracer = Tracer()
        t0 = time.perf_counter()
        with tracer.span("ckpt.fetch") as span:
            time.sleep(0.01)
            span.args["bytes"] = 5
        assert t0 <= span.start
        assert 0.01 <= span.duration_s <= time.perf_counter() - t0
        event = tracer.events[0]
        assert event["dur"] == pytest.approx(span.duration_s * 1e6)
        assert event["args"]["bytes"] == 5
        assert abs(event["ts"] / 1e6 - time.time()) < 60     # wall clock

    def test_count_is_cumulative_and_writes_on_change(self):
        tracer = Tracer()
        tracer.count("ckpt.skipped", reason="staging_in_flight")
        tracer.count("ckpt.skipped", 2, reason="staging_in_flight")
        tracer.count("ckpt.skipped", reason="lock")
        tracer.count("ckpt.skipped", 0, reason="lock")      # no change
        tracer.count("plain")
        events = tracer.events
        assert [e["ph"] for e in events] == ["C"] * 4
        assert events[2]["args"] == {
            "reason=staging_in_flight": 3, "reason=lock": 1
        }
        assert events[3]["name"] == "plain"
        assert events[3]["args"] == {"value": 1}
        assert not hasattr(tracer, "counter")

    def test_the_fork_hook_refreshes_the_cached_pid(self, monkeypatch):
        tracer = Tracer()
        tracer.instant("here")
        monkeypatch.setattr(tracing, "_pid", tracing._pid)  # restored after
        monkeypatch.setattr(os, "getpid", lambda: 424242)  # "forked"
        tracing._forked()   # what os.register_at_fork runs in the child
        tracer.instant("there")
        assert [e["pid"] for e in tracer.events][1] == 424242
        assert tracer.events[0]["pid"] != 424242

    def test_the_pid_is_taken_when_the_event_is_made(self):
        """A child forked from a process whose tracer has recorded (the
        fork server's workers) stamps its own pid, not its parent's."""
        import warnings

        tracer = Tracer()
        tracer.instant("parent")
        read, write = os.pipe()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # fork beside JAX's threads
            child = os.fork()
        if child == 0:
            try:
                tracer.instant("child")
                os.write(write, json.dumps(
                    [os.getpid(), tracer.events[-1]["pid"]]
                ).encode())
            finally:
                os._exit(0)
        os.close(write)
        with os.fdopen(read) as f:
            own, stamped = json.loads(f.read())
        os.waitpid(child, 0)
        assert stamped == own != os.getpid()
        assert tracer.events[-1]["pid"] == os.getpid()


def _worker_env(monkeypatch, path, rank=1, restart=2):
    monkeypatch.setenv("DLROVER_TPU_TRACE_FILE", path)
    monkeypatch.setenv("DLROVER_TPU_LOCAL_RANK", str(rank))
    monkeypatch.setenv("DLROVER_TPU_RESTART_COUNT", str(restart))


class TestTracerFiles:
    def test_a_workers_file_is_per_rank_and_restart(self, tmp_path,
                                                     monkeypatch):
        agent_path = str(tmp_path / "agent_trace.json")
        _worker_env(monkeypatch, agent_path)
        tracer = Tracer()
        with tracer.span("trainer.step", step=1):
            tracer.count("ckpt.skipped", reason="lock")
        tracer.instant("ckpt.io", op="staging")
        mine = tmp_path / "agent_trace.worker1.2.jsonl"
        lines = mine.read_text().splitlines()
        assert [json.loads(line)["name"] for line in lines] == [
            "ckpt.skipped", "trainer.step", "ckpt.io"
        ]
        assert json.loads(lines[1]) == json.loads(
            json.dumps(tracer.events[1])
        )
        # The agent's path is the agent's: a worker exports nothing onto
        # it, at exit or on demand.
        assert tracer.export() is None
        assert not os.path.exists(agent_path)
        # Another restart, another file; the first is left as it was.
        monkeypatch.setenv("DLROVER_TPU_RESTART_COUNT", "3")
        Tracer().instant("again")
        assert len(mine.read_text().splitlines()) == 3
        assert (tmp_path / "agent_trace.worker1.3.jsonl").exists()

    def test_a_threads_lines_go_out_when_its_outermost_span_closes(
        self, tmp_path, monkeypatch
    ):
        """One write a step, not one a record; a span that stays open
        holds back a bounded number of lines."""
        _worker_env(monkeypatch, str(tmp_path / "t.json"), rank=0, restart=0)
        mine = tmp_path / "t.worker0.0.jsonl"
        tracer = Tracer()

        def lines():
            return mine.read_text().splitlines() if mine.exists() else []

        with tracer.span("trainer.step", step=1):
            with tracer.span("trainer.input"):
                pass
            tracer.count("ckpt.skipped", reason="lock")
            assert lines() == []
        assert [json.loads(x)["name"] for x in lines()] == [
            "trainer.input", "ckpt.skipped", "trainer.step"
        ]
        with tracer.span("trainer.step", step=2):
            for _ in range(tracing._MAX_PENDING + 5):
                tracer.instant("e")
            assert len(lines()) == 3 + tracing._MAX_PENDING
        assert len(lines()) == 3 + tracing._MAX_PENDING + 5 + 1
        assert len(tracer.events) == len(lines())

    def test_the_agent_and_a_script_export_the_ring(self, tmp_path,
                                                    monkeypatch):
        path = str(tmp_path / "trace.json")
        monkeypatch.setenv("DLROVER_TPU_TRACE_FILE", path)
        monkeypatch.delenv("DLROVER_TPU_LOCAL_RANK", raising=False)
        monkeypatch.delenv("DLROVER_TPU_RESTART_COUNT", raising=False)
        tracer = Tracer()
        with tracer.span("rendezvous", round=1):
            pass
        assert os.listdir(tmp_path) == []        # nothing as it happens
        assert tracer.export() == path
        assert [e["name"] for e in tracing.read_events(path)] == [
            "rendezvous"
        ]

    def test_without_the_variable_nothing_is_written(self, tmp_path,
                                                     monkeypatch):
        monkeypatch.delenv("DLROVER_TPU_TRACE_FILE", raising=False)
        monkeypatch.setenv("DLROVER_TPU_LOCAL_RANK", "0")
        monkeypatch.setenv("DLROVER_TPU_RESTART_COUNT", "0")
        monkeypatch.chdir(tmp_path)
        tracer = Tracer()
        with tracer.span("trainer.step"):
            pass
        assert len(tracer.events) == 1 and os.listdir(tmp_path) == []

    def test_a_killed_workers_file_is_line_complete(self, tmp_path):
        """SIGKILL while the child writes spans as fast as it can: every
        line that reached the file is whole."""
        path = str(tmp_path / "t.json")
        code = (
            "import sys\n"
            "from dlrover_tpu.utils.tracing import get_tracer\n"
            "tracer = get_tracer()\n"
            "for i in range(200):\n"
            "    with tracer.span('trainer.step', step=i): pass\n"
            "print('ready', flush=True)\n"
            "while True:\n"
            "    with tracer.span('trainer.step', step=-1, pad='x' * 300):\n"
            "        pass\n"
        )
        env = dict(
            os.environ, PYTHONPATH=ROOT, DLROVER_TPU_TRACE_FILE=path,
            DLROVER_TPU_LOCAL_RANK="0", DLROVER_TPU_RESTART_COUNT="0",
        )
        child = subprocess.Popen([sys.executable, "-c", code], env=env,
                                 stdout=subprocess.PIPE, text=True)
        try:
            assert child.stdout.readline().strip() == "ready"
            time.sleep(0.05)
        finally:
            os.kill(child.pid, signal.SIGKILL)
            child.wait()
        raw = (tmp_path / "t.worker0.0.jsonl").read_bytes()
        assert raw.endswith(b"\n")
        events = [json.loads(line) for line in raw.splitlines()]
        assert len(events) > 200
        assert [e["args"]["step"] for e in events[:200]] == list(range(200))
        assert {e["pid"] for e in events} == {child.pid}
        assert not os.path.exists(path)     # killed: no export at exit
        assert tracing.read_events(str(tmp_path / "t.worker0.0.jsonl")) == events

    def test_threads_lose_no_count_id_or_line(self, tmp_path, monkeypatch):
        """More threads than cores, a short switch interval: the counter
        totals, the ids and the file's lines stay whole."""
        _worker_env(monkeypatch, str(tmp_path / "t.json"), rank=0, restart=0)
        tracer = Tracer(capacity=1 << 20)
        n_threads, each = 4 * (os.cpu_count() or 4), 300

        def work():
            for i in range(each):
                with tracer.span("trainer.step", step=i):
                    tracer.count("ckpt.skipped", reason="lock")

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=work) for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60.0)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        total = n_threads * each
        lines = (tmp_path / "t.worker0.0.jsonl").read_bytes().splitlines()
        events = [json.loads(line) for line in lines]
        assert len(events) == len(tracer.events) == 2 * total
        counts = [e["args"]["reason=lock"] for e in events if e["ph"] == "C"]
        assert sorted(counts) == list(range(1, total + 1))
        ids = [e["args"]["id"] for e in events if e["ph"] == "X"]
        assert len(set(ids)) == total

    def test_a_cut_last_line_is_dropped_by_the_reader(self, tmp_path):
        path = tmp_path / "t.worker0.0.jsonl"
        path.write_text('{"name": "a", "ph": "i"}\n{"name": "b", "p')
        assert [e["name"] for e in tracing.read_events(str(path))] == ["a"]

    def test_the_timeline_merges_a_workers_file(self, tmp_path):
        from dlrover_tpu.observability.timeline import write_chrome_trace

        agent = tmp_path / "agent.json"
        agent.write_text(json.dumps({"traceEvents": [
            {"name": "rendezvous", "ph": "X", "ts": 5.0, "dur": 1.0}
        ]}))
        worker = tmp_path / "agent.worker0.0.jsonl"
        worker.write_text(
            '{"name": "trainer.step", "ph": "X", "ts": 7.0, "dur": 1.0}\n'
            '{"name": "ckpt.stage", "ph": "X", "ts": 3.0, "dur": 1.0}\n'
        )
        out = str(tmp_path / "merged.json")
        assert write_chrome_trace([], [str(agent), str(worker)], out) == 3
        with open(out) as f:
            names = [e["name"] for e in json.load(f)["traceEvents"]]
        assert names == ["ckpt.stage", "rendezvous", "trainer.step"]


class TestTracerAndJax:
    def test_tracing_alone_leaves_jax_out(self):
        code = (
            "import sys\n"
            "from dlrover_tpu.utils.tracing import get_tracer\n"
            "with get_tracer().span('rendezvous'): pass\n"
            "get_tracer().count('c')\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", code], env=dict(os.environ, PYTHONPATH=ROOT),
            capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 0, r.stderr[-2000:]

    def test_a_span_sits_in_the_profilers_trace_under_its_name(
        self, tmp_path
    ):
        from jax.profiler import ProfileData

        tracer = Tracer()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with tracer.span("trainer.step", step=3):
                with tracer.span("trainer.dispatch"):
                    jax.block_until_ready(jnp.ones(8) * 2)
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        profile = ProfileData.from_file(found[0])
        seen = {}
        for plane in profile.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name in ("trainer.step", "trainer.dispatch"):
                            seen[e.name] = (e.start_ns, e.duration_ns,
                                            dict(e.stats))
        assert set(seen) == {"trainer.step", "trainer.dispatch"}
        assert seen["trainer.dispatch"][2]["step"] == 3
        outer, inner = seen["trainer.step"], seen["trainer.dispatch"]
        assert outer[0] <= inner[0]
        assert inner[0] + inner[1] <= outer[0] + outer[1]
        # The ring holds the same two, on the wall clock.
        assert [e["name"] for e in tracer.events] == [
            "trainer.dispatch", "trainer.step"
        ]


# ------------------------------------------------- the spans' call sites

CALL = re.compile(r"\.(?:span|count|named_scope)\(\s*\"([^\"]+)\"")


class TestSpanTable:
    def test_call_sites_and_the_table_name_the_same_spans(self):
        called = set()
        for path in glob.glob(os.path.join(ROOT, "dlrover_tpu", "**", "*.py"),
                              recursive=True):
            if path.endswith(os.path.join("utils", "tracing.py")):
                continue
            with open(path) as f:
                called |= set(CALL.findall(f.read()))
        # A step's own counters reach the tracer by the names its loss
        # gave them (train/trainer.py, _raise_counters): the names are
        # the model's.
        from dlrover_tpu.ops.moe import COUNTERS

        called |= {name.partition("{")[0] for name in COUNTERS}
        assert called == set(SPANS)

    def test_the_docs_table_has_every_row(self):
        with open(os.path.join(ROOT, "docs", "observability.md")) as f:
            doc = f.read()
        for name, (layer, thread, covers) in SPANS.items():
            assert f"| `{name}` | {layer} | {thread} | {covers} |" in doc

    def test_no_name_reads_as_the_benchmarks_or_the_runtimes(self):
        """``benchmark/xplane.py`` names idle gaps by host events that
        start with ``bench.``/``Pjit`` or contain these words."""
        for name, (layer, thread, covers) in SPANS.items():
            assert not name.startswith(("bench.", "Pjit"))
            assert not any(w in name for w in
                           ("Execute", "TransferTo", "TransferFrom"))
            assert layer and thread and covers


@pytest.fixture
def tracer(monkeypatch):
    """A fresh tracer behind ``get_tracer()`` for the call sites."""
    fresh = Tracer()
    monkeypatch.setattr(tracing, "_tracer", fresh)
    return fresh


def _tiny_trainer(**kwargs):
    import optax

    from dlrover_tpu.accel import ParallelSpec
    from dlrover_tpu.models.gpt import GPT
    from dlrover_tpu.train.trainer import Trainer
    from tests.test_trainer import batches, tiny_cfg, token_loss

    cfg = tiny_cfg()
    trainer = Trainer(
        GPT(cfg), optax.adamw(1e-3), token_loss, next(batches(cfg)),
        spec=ParallelSpec(), **kwargs,
    )
    return trainer, lambda: batches(cfg)


LOOP_STEP = [
    "trainer.step", "trainer.input", "input.host_next", "input.device_put",
    "trainer.dispatch", "trainer.save", "trainer.report", "trainer.fence",
    "trainer.readback", "trainer.callbacks",
]


class TestLoopSpans:
    @pytest.mark.parametrize("pipeline", [True, False])
    def test_three_steps_emit_the_loop_spans_in_order(
        self, tracer, job_name, tmp_path, pipeline
    ):
        trainer, batches = _tiny_trainer(
            checkpoint_dir=str(tmp_path / "flash"), persist_every=1000
        )
        try:
            trainer.fit(batches(), steps=3, pipeline=pipeline)
            assert trainer.checkpointer.engine.wait_staged(30.0)
        finally:
            trainer.close()
        spans = {e["args"]["id"]: e for e in tracer.events if e["ph"] == "X"}
        steps = [e for e in spans.values() if e["name"] == "trainer.step"]
        assert [e["args"]["step"] for e in steps] == [1, 2, 3]
        loop = [
            e for _, e in sorted(spans.items())
            if e["tid"] == steps[0]["tid"]
        ]       # ids are given as spans open: the order they began in
        names = [e["name"] for e in loop if not e["name"].startswith("ckpt.")]
        per_step = [n for n in LOOP_STEP if pipeline or not n.startswith("input.")]
        first_fill = ["input.host_next", "input.device_put"] * 2
        # fit() resumes first; with nothing to restore, nothing is put.
        assert names == (
            ["trainer.restore"] + (first_fill if pipeline else [])
            + per_step * 3
        )
        restore = next(e for e in loop if e["name"] == "trainer.restore")
        assert restore["args"]["step"] == -1
        assert spans[restore["args"]["id"] + 1]["name"] == "ckpt.restore"
        # The first offer is taken, on the loop thread, inside the save.
        taken = [e for e in loop if e["name"] == "ckpt.snapshot"]
        assert taken and taken[0]["args"]["step"] == 1
        assert spans[taken[0]["args"]["parent"]]["name"] == "trainer.save"
        own = next(e for e in loop if e["name"] == "ckpt.own_copies")
        assert own["args"]["parent"] == taken[0]["args"]["id"]

        def under_a_step(e):
            while "parent" in e["args"]:
                e = spans[e["args"]["parent"]]
            return e["name"] == "trainer.step"

        begin = steps[0]["ts"]
        end = steps[-1]["ts"] + steps[-1]["dur"]
        inside = [e for e in loop if begin <= e["ts"] <= end]
        assert len(inside) >= 3 * len(per_step)
        assert all(under_a_step(e) for e in inside)
        # ... and each says which step it belongs to.
        assert all(e["args"]["step"] in (1, 2, 3) for e in inside)
        before = [
            e for e in loop if e["ts"] < begin
            and not e["name"].startswith(("trainer.restore", "ckpt.restore"))
        ]
        assert [e["name"] for e in before] == (first_fill if pipeline else [])
        assert not any(under_a_step(e) for e in before)

    def test_step_phases_carry_the_spans_durations(self, tracer, job_name):
        from dlrover_tpu.observability import events as events_mod
        from dlrover_tpu.observability.event_log import EventLog
        from dlrover_tpu.observability.events import EventKind

        log = EventLog()
        events_mod.install_sink(log.append)
        try:
            trainer, batches = _tiny_trainer()
            trainer.fit(batches(), steps=3)
        finally:
            events_mod.reset()
        phases = log.events(kinds=[EventKind.STEP_PHASES])
        assert [e.args["step"] for e in phases] == [1, 2, 3]
        spans = {
            (e["name"], e["args"]["step"]): e["dur"] / 1e6
            for e in tracer.events if e["ph"] == "X" and "step" in e["args"]
        }
        for e in phases:
            assert set(e.args) == {
                "step", "step_s", "input_s", "compute_s", "collective_s",
                "readback_s",
            }       # the fields the straggler detector has always read
            n = e.args["step"]
            assert e.args["input_s"] == pytest.approx(
                spans["trainer.input", n])
            assert e.args["readback_s"] == pytest.approx(
                spans["trainer.readback", n])
            assert e.args["compute_s"] + e.args["collective_s"] == (
                pytest.approx(spans["trainer.dispatch", n]
                              + spans["trainer.fence", n])
            )


STAGING = ["ckpt.stage", "ckpt.fetch", "ckpt.lock_wait", "ckpt.shm_copy",
           "ckpt.shm_flush", "ckpt.publish"]


class TestSnapshotSpans:
    @pytest.fixture
    def engine(self, job_name, tmp_path):
        from dlrover_tpu.common.ckpt_meta import ckpt_shm_name
        from dlrover_tpu.common.shared_memory import SharedMemory
        from dlrover_tpu.train.checkpoint import CheckpointEngine

        engine = CheckpointEngine(str(tmp_path / "ckpts"))
        yield engine
        engine.close()
        SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))

    STATE = {"w": jnp.ones((64, 64), jnp.float32), "b": jnp.zeros((128,))}
    NBYTES = 64 * 64 * 4 + 128 * 4

    def test_one_async_snapshot_emits_the_staging_spans_once(
        self, tracer, engine
    ):
        assert engine.save_to_memory_async(5, self.STATE)
        assert engine.wait_staged(30.0)
        spans = [e for e in tracer.events if e["ph"] == "X"]
        by_name = {e["name"]: e for e in spans}
        assert sorted(e["name"] for e in spans) == sorted(
            STAGING + ["ckpt.snapshot", "ckpt.own_copies"]
        )
        assert all(e["args"]["step"] == 5 for e in spans)
        stage = by_name["ckpt.stage"]
        for name in STAGING[1:]:
            assert by_name[name]["args"]["parent"] == stage["args"]["id"]
            assert by_name[name]["tid"] == stage["tid"]
        assert by_name["ckpt.snapshot"]["tid"] != stage["tid"]
        assert stage["args"]["bytes"] == self.NBYTES
        assert by_name["ckpt.fetch"]["args"]["bytes"] == self.NBYTES
        assert by_name["ckpt.own_copies"]["args"]["bytes"] == self.NBYTES
        assert by_name["ckpt.shm_copy"]["args"]["bytes"] >= self.NBYTES
        assert by_name["ckpt.fetch"]["args"]["chunks"] == 1
        # The ckpt.io staging event keeps its fields; its time is the
        # fetch span's.
        io = [e for e in tracer.events if e["name"] == "ckpt.io"]
        assert len(io) == 1 and io[0]["args"]["op"] == "staging"
        assert io[0]["args"]["bytes"] == self.NBYTES
        assert io[0]["args"]["duration_s"] == round(
            by_name["ckpt.fetch"]["dur"] / 1e6, 4
        )
        assert engine.cached_step == 5

    def test_an_offer_while_one_stages_is_counted_as_skipped(
        self, tracer, engine, monkeypatch
    ):
        release = threading.Event()
        fetch = engine._fetch

        def slow_fetch(blocks, step=-1):
            release.wait(30.0)
            return fetch(blocks, step)

        monkeypatch.setattr(engine, "_fetch", slow_fetch)
        assert engine.save_to_memory_async(1, self.STATE)
        assert not engine.save_to_memory_async(2, self.STATE)
        assert not engine.save_to_memory_async(3, self.STATE)
        release.set()
        assert engine.wait_staged(30.0)
        counts = [e for e in tracer.events if e["ph"] == "C"]
        assert [e["name"] for e in counts] == ["ckpt.skipped"] * 2
        assert counts[-1]["args"] == {"reason=staging_in_flight": 2}
        assert engine.cached_step == 1
        taken = [e for e in tracer.events if e["name"] == "ckpt.snapshot"]
        assert [e["args"]["step"] for e in taken] == [1]

    def test_a_superseded_snapshot_is_counted_and_holds_no_lock(
        self, tracer, engine
    ):
        assert engine.save_to_memory(2, self.STATE)
        blocks, objects = engine._snapshot(self.STATE, own=False, step=1)
        arrays = engine._fetch(blocks, 1)
        assert not engine._write_snapshot(1, blocks, arrays, objects,
                                          True, gen=0)
        counts = [e for e in tracer.events if e["ph"] == "C"]
        assert counts[-1]["args"] == {"reason=superseded": 1}
        assert engine.cached_step == 2
        assert engine._write_mutex.acquire(blocking=False)
        engine._write_mutex.release()
        # A sync save runs the same spans on the caller's thread.
        names = {e["name"] for e in tracer.events if e["ph"] == "X"}
        assert names == set(STAGING[1:]) | {"ckpt.snapshot"}


class TestModuleCosts:
    """Per-module attribution (parity with AProfiler's
    module table ``atorch/atorch/utils/prof.py:39-464``)."""

    def test_ranks_transformer_blocks_dominant(self):
        import dataclasses

        import jax
        import jax.numpy as jnp

        from dlrover_tpu.models.gpt import GPT, GPTConfig
        from dlrover_tpu.utils.profiler import Profiler

        cfg = dataclasses.replace(
            GPTConfig.tiny(), dtype=jnp.float32, scan_layers=False
        )
        prof = Profiler()
        tokens = jnp.zeros((2, 16), jnp.int32)
        rows = prof.module_costs(
            GPT(cfg), jax.random.PRNGKey(0), tokens, depth=2
        )
        assert rows, "no module rows recorded"
        by_path = {r["path"]: r for r in rows}
        # Transformer blocks must dominate the norms/embeddings...
        assert by_path["block_0"]["flops"] > by_path["ln_f"]["flops"]
        # ...and within a block the MLP up-projection (d->4d) must
        # outrank qkv (d->3d): the compiler's numbers, not guesses.
        assert (
            by_path["block_0/up"]["flops"]
            > by_path["block_0/qkv"]["flops"]
        )
        # shares are normalized against the root total
        top = rows[0]
        assert 0 < top["share"] <= 1.0

    def test_scan_module_reports_whole_stack(self):
        import dataclasses

        import jax
        import jax.numpy as jnp

        from dlrover_tpu.models.gpt import GPT, GPTConfig
        from dlrover_tpu.utils.profiler import Profiler

        unrolled = dataclasses.replace(
            GPTConfig.tiny(), dtype=jnp.float32, scan_layers=False
        )
        scanned = dataclasses.replace(unrolled, scan_layers=True)
        prof = Profiler()
        tokens = jnp.zeros((2, 16), jnp.int32)
        rows_u = prof.module_costs(
            GPT(unrolled), jax.random.PRNGKey(0), tokens, depth=1
        )
        rows_s = prof.module_costs(
            GPT(scanned), jax.random.PRNGKey(0), tokens, depth=1
        )
        flops_u = sum(
            r["flops"] for r in rows_u if r["path"].startswith("block_")
        )
        blocks = next(r for r in rows_s if r["path"] == "blocks")
        # XLA's cost analysis counts a while-loop body ONCE, so the
        # scanned row reports per-iteration cost: total / num_layers
        # (module_costs documents this; unrolled configs give totals).
        assert blocks["flops"] == pytest.approx(
            flops_u / unrolled.num_layers, rel=0.05
        )
