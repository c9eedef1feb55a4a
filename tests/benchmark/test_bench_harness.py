"""The harness around the cells: traffic from a seed, the arithmetic from
records to end-to-end metrics and ``correct``, cells found by name, a new
cell added as files only, and the command's contract. What starts the
launcher and lasts more than seconds is ``slow``."""

import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cells, end_to_end, traffic
from benchmark import run as bench_run

ROOT = cells.ROOT
RUN = os.path.join(ROOT, "benchmark", "run.py")


# ---------------------------------------------------------------- traffic

class TestTraffic:
    DATA = {"sequences": 64, "tokens": {"distribution": "zipf", "a": 1.2},
            "documents": {"mean_length": 40, "sigma": 1.0, "eos_id": 0}}

    def test_the_same_seed_gives_the_same_tokens(self):
        a = traffic.make_dataset(self.DATA, 128, 512, seed=3)
        b = traffic.make_dataset(self.DATA, 128, 512, seed=3)
        c = traffic.make_dataset(self.DATA, 128, 512, seed=4)
        assert a.dtype == np.int32 and a.shape == (64, 128)
        assert np.array_equal(a, b) and not np.array_equal(a, c)
        assert a.min() >= 0 and a.max() < 512

    def test_zipf_ids_are_skewed_and_documents_end_in_eos(self):
        a = traffic.make_dataset(self.DATA, 128, 512, seed=0)
        counts = np.bincount(a.ravel(), minlength=512)
        assert counts[:8].sum() > counts[256:].sum() * 2
        plain = traffic.make_dataset(
            {"sequences": 64, "tokens": {"distribution": "uniform"}},
            128, 512, seed=0,
        )
        flat = np.bincount(plain.ravel(), minlength=512)
        assert flat.max() < 4 * flat.mean()
        # About one end-of-document in 40 tokens on top of the zipf zeros.
        extra = (a == 0).mean() - (
            traffic.make_dataset(
                dict(self.DATA, documents=None), 128, 512, seed=0
            ) == 0
        ).mean()
        assert 0.5 / 40 < extra < 2.0 / 40

    def test_an_unknown_distribution_is_refused(self):
        with pytest.raises(ValueError):
            traffic.make_dataset(
                {"sequences": 1, "tokens": {"distribution": "x"}}, 8, 16, 0
            )

    def test_a_resumed_stream_carries_on_where_it_was(self):
        from dlrover_tpu.train.data import ElasticDataLoader, ElasticSampler

        data = traffic.make_dataset(
            {"sequences": 10, "tokens": {"distribution": "uniform"}},
            8, 100, seed=1,
        )

        def stream(start):
            sampler = ElasticSampler(10, shuffle=True, seed=1, drop_last=True)
            loader = ElasticDataLoader(
                traffic.TokenDataset(data), batch_size=4, sampler=sampler,
                drop_last=True,
            )
            return traffic.epochs(loader, sampler, 4, start)

        fresh = stream(0)
        seen = [next(fresh) for _ in range(7)]      # 2 batches an epoch
        assert all(b.shape == (4, 8) for b in seen)
        assert not np.array_equal(seen[0], seen[2])  # reshuffled
        resumed = stream(3)
        for want in seen[3:]:
            assert np.array_equal(next(resumed), want)


# ---------------------------------------------------- records to metrics

def _flush(**over):
    """A window of 4 steps of 0.5 s closed by the clock: opened at perf
    10.0 with step 3's stamp (the end of step 2); closed at 12.0, drained,
    with a stamp of its own for the end of step 6."""
    stamps = [[n, 8.5 + 0.5 * n, 1000.0 + 8.5 + 0.5 * n,
               None if n == 1 else 5.0] for n in range(1, 8)]
    base = {
        "event": "done", "incarnation": 0, "stamps": stamps,
        "t_open": 10.0, "t_close": 12.0, "t_open_wall": 1010.0,
        "t_close_wall": 1012.0, "tokens_per_step": 4096,
        "open_compiles": {"compile_requests": 25, "cache_misses": 0},
        "close_compiles": {"compile_requests": 25, "cache_misses": 0},
        "dispatched": [], "landed": [], "cache_misses": 0,
        "memory": [{"peak_bytes_in_use": 5}],
    }
    base.update(over)
    return base


REFERENCE = {
    "event": "reference", "incarnation": 0, "loss_ref_batch": 5.0,
    "agreement": {"embed": {"cosine": 0.9991, "norm_ratio": 0.98},
                  "layer0": {"cosine": 0.99998, "norm_ratio": 0.999}},
    "tolerance": {
        "loss_abs": 0.01,
        "embed": {"cosine_min": 0.997, "norm_ratio": [0.95, 1.05]},
        "layer0": {"cosine_min": 0.9999, "norm_ratio": [0.99, 1.01]},
    },
}
STEADY = {"job": {"checkpoint": {"enabled": False}, "kill": False}}
ELASTIC = {"job": {"checkpoint": {"enabled": True}, "kill": True}}


class TestEndToEnd:
    def test_tokens_per_s_counts_step_ends_inside_the_window(self):
        # Ends inside (10.0, 12.0]: steps 3..6 at 10.5..12.0; from the
        # first such end to the last are 3 steps in 1.5 s.
        flush = _flush()
        assert end_to_end.window_steps(flush) == (3, 6)
        assert end_to_end.tokens_per_s(flush) == 3 * 4096 / 1.5
        assert end_to_end.tokens_per_s(_flush(t_close=None)) is None
        assert end_to_end.tokens_per_s(_flush(t_close=10.6)) is None

    def test_setup_is_start_to_open_and_only_the_cells_metrics(self):
        cell = dict(STEADY, end_to_end=[
            {"name": "setup_s", "unit": "s"},
            {"name": "tokens_per_s", "unit": "tokens/s"},
            {"name": "staging_tokens_per_s", "unit": "tokens/s"},
            {"name": "another_s", "unit": "s"},  # nothing to read: left out
        ])
        got = end_to_end.metrics(cell, [_flush()], t_start=980.0)
        assert got == {
            "setup_s": {"value": 30.0, "unit": "s"},
            "tokens_per_s": {"value": 8192.0, "unit": "tokens/s"},
            "staging_tokens_per_s": {"value": 8192.0, "unit": "tokens/s"},
        }

    def _elastic_records(self):
        flush = _flush(
            event="kill", snapshot_step=5, fingerprint=[1, 2], t_kill=1012.0,
            # A window of whole snapshot cycles: step 1's landing opened
            # it; step 3's landed 0.8 s after its dispatch, step 5's 0.9 s
            # after and closed it; step 6's was in flight at the kill.
            dispatched=[[1, 1009.2], [3, 1010.1], [5, 1011.1], [6, 1011.95]],
            landed=[[1, 1009.99], [3, 1010.9], [5, 1012.0]],
        )
        return [
            REFERENCE, flush,
            {"event": "resume", "incarnation": 1, "step": 5,
             "fingerprint": [1, 2]},
            {"event": "first_step", "incarnation": 1, "t_done": 1062.0},
            {"event": "done", "incarnation": 1, "cache_misses": 0,
             "memory": [{}]},
        ]

    def test_snapshot_s_and_resume_s(self):
        recs = self._elastic_records()
        times = end_to_end.snapshot_times(recs[1])
        assert times == pytest.approx([0.8, 0.9])
        assert end_to_end.snapshot_s(recs[1]) == pytest.approx(0.85)
        # Whole cycles: from the window's first landing (1010.9) to its
        # last (1012.0) lie the ends of steps 4 and 5, 0.5 s apart.
        assert end_to_end.snapshotting_tokens_per_s(recs[1]) == 8192.0
        assert end_to_end.staging_tokens_per_s(recs[1]) == 8192.0
        # One stalled step moves the median of the window's steps little
        # and its total much.
        stalled = copy.deepcopy(recs[1])
        for stamp in stalled["stamps"][5:]:
            stamp[1] += 0.3
        stalled["t_close"] += 0.3
        assert end_to_end.staging_tokens_per_s(stalled) == 8192.0
        assert end_to_end.tokens_per_s(stalled) == pytest.approx(
            3 * 4096 / 1.8
        )
        # (each of the three dispatches has the slow step among the four
        # that follow it)
        assert end_to_end.dispatch_stalls(stalled) == pytest.approx(
            [0.3, 0.3, 0.3]
        )
        assert end_to_end.resume_s(recs) == 50.0
        assert end_to_end.resume_s(recs[:3]) is None

    def test_a_good_run_is_correct_and_counts_what_it_attempted(self):
        assert end_to_end.judge(STEADY, [REFERENCE, _flush()]) == {
            "correct": True, "attempted": 3, "failed": 0, "why": [],
        }
        got = end_to_end.judge(ELASTIC, self._elastic_records())
        # 3 steps, the 3 snapshots dispatched in the window, 1 resume
        assert got == {"correct": True, "attempted": 7, "failed": 0,
                       "why": []}

    @pytest.mark.parametrize("change, reason, failed", [
        (lambda r: r[1].update(close_compiles={"compile_requests": 26,
                                               "cache_misses": 0}),
         "compiled or loaded inside the window", 0),
        (lambda r: r[1]["stamps"][5].__setitem__(3, float("nan")),
         "non-finite", 1),
        (lambda r: r[0].update(loss_ref_batch=5.1), "first-step loss", 0),
        (lambda r: r[0]["agreement"]["embed"].update(cosine=0.99),
         "embed gradient cosine", 0),
        # what int8 MLP matmuls read on the chip at full size (PERF.md)
        (lambda r: r[0]["agreement"]["layer0"].update(cosine=0.99984),
         "layer0 gradient cosine", 0),
        (lambda r: r[0]["agreement"]["layer0"].update(norm_ratio=1.02),
         "layer0 gradient norm ratio", 0),
        (lambda r: r[1].update(landed=[[1, 1009.99], [5, 1012.0]]),
         "never landed", 1),
        (lambda r: r[1].update(landed=[]), "no snapshot landed", 3),
        (lambda r: r[2].update(step=4), "resumed at step 4", 1),
        (lambda r: r[2].update(fingerprint=[1, 3]), "fingerprint", 1),
        (lambda r: r[4].update(cache_misses=2), "compiled 2 new", 0),
        (lambda r: r.pop(3), "did not resume", 1),
        (lambda r: r.__delitem__(slice(2, 5)), "did not resume", 1),
    ])
    def test_what_makes_a_run_incorrect(self, change, reason, failed):
        recs = copy.deepcopy(self._elastic_records())
        change(recs)
        got = end_to_end.judge(ELASTIC, recs)
        assert not got["correct"] and got["failed"] == failed
        assert any(reason in w for w in got["why"]), got["why"]

    def test_a_window_that_never_closed_is_no_result(self):
        got = end_to_end.judge(STEADY, [REFERENCE, _flush(t_close=None)])
        assert got["correct"] is False and got["attempted"] == 0


# ------------------------------------------------------------- the cells

class TestCells:
    def test_a_cell_is_found_by_name_with_its_files(self):
        cell = cells.resolve("gpt2-xl.elastic", ROOT)
        assert cell["family"] == "gpt2" and cell["chips"] == 1
        assert cell["config"]["n_embd"] == 1600
        assert cell["job"]["kill"] and cell["job"]["checkpoint"]["enabled"]
        assert {m["name"] for m in cell["end_to_end"]} == {
            "staging_tokens_per_s", "setup_s"
        }
        assert {"agent.resume_s", "ckpt.snapshot_s"} <= {
            m["name"] for m in cell["per_layer"]
        }
        steady = cells.resolve("gpt2-xl.steady", ROOT)
        # A per-layer metric goes where the metric it moves is reported.
        names = {m["name"] for m in steady["per_layer"]}
        assert "agent.crash_flush_s" not in names
        assert "device.idle_share" in names
        with pytest.raises(cells.CellError):
            cells.resolve("no-such-cell", ROOT)

    def test_rehearsal_lays_toy_sizes_over_the_files(self):
        cell = cells.resolve("mistral-7b.long16k", ROOT, rehearsal=True)
        assert cell["config"]["hidden_size"] == 64
        assert cell["config"]["rope_theta"] == 1000000.0    # kept
        assert cell["job"]["sequence"] == 512
        assert cell["job"]["attention"] == {
            "impl": "pallas", "block_q": 128, "block_k": 128
        }
        assert "rehearsal" not in cell["config"]
        real = cells.resolve("mistral-7b.long16k", ROOT)
        assert real["config"]["hidden_size"] == 4096


@pytest.fixture
def extended_copy(tmp_path):
    """A copy of the benchmark with a new config, a new job, a new
    per-layer metric and one more ``workloads`` entry: files added, none
    that was there edited (``BENCHMARK.json`` gains entries)."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {
        p: p.read_bytes() for p in (root / "benchmark").rglob("*")
        if p.is_file()
    }
    bench = cells.load_benchmark(ROOT)
    config = json.loads(
        (root / "benchmark/configs/gpt2-xl.json").read_text()
    )
    config.update(n_layer=24, n_embd=1024, n_head=16,
                  source="https://huggingface.co/openai-community/gpt2-medium/blob/main/config.json")
    (root / "benchmark/configs/gpt2-medium.json").write_text(
        json.dumps(config)
    )
    job = json.loads(
        (root / "benchmark/jobs/b4s1k-adam8bit.json").read_text()
    )
    job.update(batch=8, optimizer={"factory": "optax:adamw",
                                   "args": {"learning_rate": 1e-4}})
    (root / "benchmark/jobs/b8s1k-adamw.json").write_text(json.dumps(job))
    (root / "benchmark/layer_metrics/trainer.steps_in_window.py").write_text(
        '"""Steps that started inside the window."""\n\n\n'
        "def read(ctx):\n"
        '    return float(len(ctx.window_slice("input_wait")))\n'
    )
    bench["configs"].append({
        "name": "gpt2-medium", "source": config["source"],
        "file": "benchmark/configs/gpt2-medium.json", "reduced": [],
        "why": "a second size of the first family",
    })
    bench["workloads"].append({
        "name": "gpt2-medium.adamw", "config": "gpt2-medium",
        "traffic": "b8s1k-adamw", "chips": 1, "why": "shows a cell added",
    })
    # Where the new cell reports them: the rate, and the shared per-layer
    # metrics that move it (a list gains a name; no entry is rewritten).
    shared = {"tokens_per_s", "device.idle_share", "model.step_device_ms"}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] in shared:
            metric["workloads"].append("gpt2-medium.adamw")
    bench["per_layer"].append({
        "name": "trainer.steps_in_window", "unit": "steps",
        "better": "higher", "source": "program_counter",
        "layer": "trainer", "moves": "tokens_per_s",
        "workloads": ["gpt2-medium.adamw"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    yield str(root)
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"


class TestACellIsAddedAsFilesOnly:
    def test_the_new_cell_resolves_builds_and_reads_its_metric(
        self, extended_copy
    ):
        import jax

        cell = cells.resolve("gpt2-medium.adamw", extended_copy,
                             rehearsal=True)
        assert cell["bench_dir"] == os.path.join(extended_copy, "benchmark")
        assert cell["job"]["batch"] == 8
        assert {"trainer.steps_in_window", "device.idle_share",
                "model.step_device_ms", "setup.launch_s"} <= {
            m["name"] for m in cell["per_layer"]
        }
        # The new metric's reader is found by name and run by the harness
        # over a run's records; a reader with nothing to read is left out.
        flush = _flush(input_wait=[0.001] * 7)
        ctx = bench_run.Context(cell, [flush], extended_copy, None, "cpu")
        got = bench_run.per_layer(cell, ctx)
        assert got["trainer.steps_in_window"] == {
            "value": 4.0, "unit": "steps"
        }      # steps 3..6 ended in it
        assert "device.idle_share" not in got       # no trace: nothing
        # The new config and job build the program's model and a loss.
        family = cells.family_module("models", "gpt2", cell["bench_dir"])
        built = family.build(cell["config"], cell["job"])
        tokens = traffic.make_dataset(
            cell["job"]["data"], cell["job"]["sequence"],
            cell["config"]["vocab_size"], seed=0,
        )[:2]
        params = built["module"].init(jax.random.PRNGKey(0), tokens)
        loss = built["loss"](built["module"], params["params"], tokens)
        assert 5.5 < float(loss) < 7.0      # ~ ln(512) at random weights
        # At the real size the new config is its own count.
        sizes = family.sizes(
            cells.resolve("gpt2-medium.adamw", extended_copy)["config"]
        )
        assert sizes["params"] == 354_823_168   # the published 355M

    @pytest.mark.slow
    def test_the_new_cell_runs_through_the_launcher(self, extended_copy):
        os.symlink(os.path.join(ROOT, "dlrover_tpu"),
                   os.path.join(extended_copy, "dlrover_tpu"))
        r = _run(["--workload", "gpt2-medium.adamw", "--seconds", "2",
                  "--trace", "1", "--rehearsal"],
                 script=os.path.join(extended_copy, "benchmark", "run.py"))
        assert r.returncode == 0, r.stderr[-3000:]
        line = json.loads(r.stdout.strip().splitlines()[-1])
        assert line["correct"] and "trainer.steps_in_window" in line["reported"]


# ---------------------------------------------------------- the command

def _run(args, script=RUN, cwd=None, timeout=600):
    from tests.conftest import cpu_subprocess_env

    env = cpu_subprocess_env()
    env.pop("PYTHONPATH")       # the command finds its checkout itself
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


class TestCommandContract:
    def test_alone_with_its_files_it_fails_without_a_result(self, tmp_path):
        shutil.copytree(os.path.join(ROOT, "benchmark"),
                        tmp_path / "benchmark")
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
        r = _run(["--workload", "gpt2-xl.steady", "--seed", "1",
                  "--seconds", "1", "--trace", "0"],
                 script=str(tmp_path / "benchmark" / "run.py"))
        assert r.returncode != 0 and r.stdout.strip() == ""
        assert "dlrover_tpu/ is not beside benchmark/" in r.stderr

    def test_an_unknown_workload_fails_without_a_result(self):
        r = _run(["--workload", "nope", "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
        assert r.returncode != 0 and r.stdout.strip() == ""

    def test_without_a_tpu_it_fails_without_a_result(self):
        """Nothing falls back to the CPU: the worker sees platform cpu,
        refuses, and the command prints no line. (The launcher starts and
        ends in seconds.)"""
        r = _run(["--workload", "gpt2-xl.steady", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], timeout=120)
        assert r.returncode != 0
        assert r.stdout.strip() == ""
        assert "needs 1 tpu device" in r.stderr

    def test_the_parent_never_imports_jax(self):
        code = (
            "import sys; sys.argv = ['run.py', '--workload', 'nope']; "
            "import runpy\n"
            "try:\n    runpy.run_path(%r, run_name='__main__')\n"
            "except SystemExit: pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'" % RUN
        )
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr[-2000:]

    @pytest.mark.slow
    @pytest.mark.parametrize("workload, trace", [
        ("gpt2-xl.steady", 0), ("gpt2-xl.elastic", 0),
        ("gpt2-xl.elastic", 1), ("mistral-7b.long16k", 1),
        ("gpt2-xl.fsdp4", 0),
    ])
    def test_cpu_rehearsal_of_a_cell(self, workload, trace):
        """The whole control flow at toy widths: launcher, (device check,)
        fork server, worker, window, (kill, flush, restart, restore); no
        device metric is printed, only which metrics could be."""
        r = _run(["--workload", workload, "--seed", "5", "--seconds", "2",
                  "--trace", str(trace), "--rehearsal"])
        assert r.returncode == 0, r.stderr[-3000:]
        line = json.loads(r.stdout.strip().splitlines()[-1])
        assert line["rehearsal"] == "cpu" and line["correct"], r.stderr[-2000:]
        assert line["failed"] == 0 and line["attempted"] > 0
        assert "metrics" not in line and "device" not in line
        cell = cells.resolve(workload, ROOT)
        if trace:
            assert "setup.launch_s" in line["reported"]
            assert "device.idle_share" not in line["reported"]
        else:
            assert set(line["reported"]) == {
                m["name"] for m in cell["end_to_end"]
            }
