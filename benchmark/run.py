#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result as one JSON line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts the cell's job the way a user's job starts,
``python -m dlrover_tpu.cli --standalone ... benchmark/worker.py``, waits
for it, and reduces what the worker recorded. This process never imports
JAX: the chip belongs to the worker. Without a TPU (or with fewer chips
than the cell asks for) the exit code is not 0 and no result is printed;
``--rehearsal`` runs the same control flow on the CPU at toy widths and
prints counts, never a device metric.
"""

import argparse
import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

T_START = time.time()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells, end_to_end, records  # noqa: E402

# A compiling first run may take 1200 s, every other 360 s; the driver
# holds the run to those, this is only the backstop against a hang.
JOB_TIMEOUT_S = 1100


class RunFailure(Exception):
    pass


def _log(msg: str):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def _tail(path: str, n: int = 50) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(line[:400] for line in f.readlines()[-n:])
    except OSError:
        return ""


def kill_job(job: str):
    """SIGKILL whatever still carries this job's name in its environment
    (the master, the fork server and the workers run in sessions of their
    own) and wait until each has ended."""
    needle = f"DLROVER_TPU_JOB_NAME={job}".encode()
    pids = []
    for path in glob.glob("/proc/[0-9]*/environ"):
        pid = int(path.split("/")[2])
        if pid == os.getpid():
            continue
        try:
            with open(path, "rb") as f:
                if needle not in f.read().split(b"\0"):
                    continue
            os.kill(pid, signal.SIGKILL)
            pids.append(pid)
        except (OSError, ValueError):
            continue
    deadline = time.monotonic() + 20
    while pids and time.monotonic() < deadline:
        pids = [p for p in pids if _alive(p)]
        time.sleep(0.05)


def _alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except (OSError, IndexError):
        return False


def launch(cell: dict, work: str, env: dict) -> int:
    """The cell's job through the launcher; returns its exit code."""
    job = f"bench-{os.getpid()}"
    flags = cell["job"]["launcher"]
    env = dict(
        env,
        DLROVER_TPU_JOB_NAME=job,
        DLROVER_TPU_GOODPUT_JSON=os.path.join(work, "goodput.json"),
        DLROVER_TPU_TRACE_FILE=os.path.join(work, "agent_trace.json"),
    )
    cmd = [
        sys.executable, "-m", "dlrover_tpu.cli", "--standalone",
        f"--nproc_per_node={flags.get('nproc_per_node', 1)}",
        f"--max_restarts={flags.get('max_restarts', 0)}",
        f"--monitor_interval={flags.get('monitor_interval', 0.5)}",
        f"--job_name={job}", f"--log_dir={os.path.join(work, 'logs')}",
    ] + (["--network-check"] if flags.get("network_check") else []) + [
        os.path.join(cell["bench_dir"], "worker.py"), "--",
        "--cell", os.path.join(work, "cell.json"),
    ]
    try:
        with open(os.path.join(work, "launcher.log"), "wb") as log:
            proc = subprocess.Popen(
                cmd, env=env, cwd=ROOT, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
            try:
                return proc.wait(timeout=JOB_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                raise RunFailure(
                    f"the launcher did not finish in {JOB_TIMEOUT_S} s"
                )
    finally:
        kill_job(job)
        for path in glob.glob(f"/dev/shm/*{job}*"):
            try:
                os.unlink(path)
            except OSError:
                pass


def reduce_trace(work: str, env: dict):
    """The worker's profiler trace, reduced by a process of its own that
    is held to the CPU (so this one stays off JAX)."""
    trace_dir = os.path.join(work, "trace")
    if not os.path.isdir(trace_dir):
        return None
    out = os.path.join(work, "trace_reduced.json")
    r = subprocess.run(
        [sys.executable, "-m", "benchmark.xplane", "reduce", trace_dir, out],
        env=dict(env, JAX_PLATFORMS="cpu"), cwd=ROOT, capture_output=True,
        text=True, timeout=300,
    )
    if r.returncode != 0:
        _log(f"trace reduction failed:\n{r.stderr[-2000:]}")
        return None
    with open(out) as f:
        return json.load(f)


class Context:
    """What a per-layer metric's reader may read."""

    def __init__(self, cell, recs, work, trace, device_kind):
        from benchmark import costs

        self.cell, self.records, self.trace = cell, recs, trace
        self.costs, self.t_start = costs, T_START
        self.flush = end_to_end.last_flush(recs, 0)
        self.sizes = cells.family_module(
            "models", cell["family"], cell["bench_dir"]
        ).sizes(cell["config"])
        self.peaks = (
            None if cell["rehearsal"] else costs.load_peaks(device_kind)
        )
        self.goodput = self._json(os.path.join(work, "goodput.json")) or {}
        agent = self._json(os.path.join(work, "agent_trace.json")) or {}
        self.agent_spans = agent.get("traceEvents", [])

    @staticmethod
    def _json(path):
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def of(self, event, **match):
        return records.of(self.records, event, **match)

    def window_slice(self, name):
        """The per-step list ``name`` of the worker's records, cut to the
        steps that ended inside the window."""
        steps = self.flush and end_to_end.window_steps(self.flush)
        if not steps:
            return []
        return self.flush[name][steps[0] - 1:steps[1]]

    @property
    def summary(self):
        return (self.trace or {}).get("summary")


def per_layer(cell: dict, ctx: Context) -> dict:
    out = {}
    for m in cell["per_layer"]:
        path = os.path.join(cell["bench_dir"], "layer_metrics",
                            m["name"] + ".py")
        try:
            value = cells.load_module(path).read(ctx)
        except Exception as e:  # one reader's fault costs one metric
            _log(f"reader {m['name']} failed: {e!r}")
            value = None
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args) -> dict:
    cell = cells.resolve(args.workload, ROOT, rehearsal=args.rehearsal)
    if not os.path.isdir(os.path.join(ROOT, "dlrover_tpu")):
        raise RunFailure("dlrover_tpu/ is not beside benchmark/")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, env.get("PYTHONPATH", "")) if p
    )
    if args.rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell['chips']}"
        )
    work = tempfile.mkdtemp(prefix="benchmark_")
    cell.update(seed=args.seed, seconds=args.seconds, trace=args.trace,
                out=work)
    with open(os.path.join(work, "cell.json"), "w") as f:
        json.dump(cell, f)
    try:
        rc = launch(cell, work, env)
        recs = records.read(os.path.join(work, "worker.jsonl"))
        fatal = records.of(recs, "fatal")
        if fatal:
            raise RunFailure(fatal[0]["error"])
        starts = records.of(recs, "start", incarnation=0)
        flush = end_to_end.last_flush(recs, 0)
        if rc != 0 or not starts or flush is None or not flush["t_close"]:
            raise RunFailure(f"the job exited {rc} without a closed window")
        verdict = end_to_end.judge(cell, recs)
        for reason in verdict.pop("why"):
            _log(f"not correct: {reason}")
        trace = reduce_trace(work, env) if args.trace else None
        start = starts[0]
        ctx = Context(cell, recs, work, trace, start["device_kind"])
        if args.trace:
            metrics = per_layer(cell, ctx)
        else:
            metrics = end_to_end.metrics(cell, recs, T_START)
        if args.rehearsal:
            return {"rehearsal": "cpu", **verdict,
                    "reported": sorted(metrics)}
        peak = max(
            (m.get("peak_bytes_in_use", 0) for r in recs
             if r["event"] in end_to_end.FLUSHES for m in r["memory"]),
            default=0,
        )
        device = {
            "platform": start["platform"], "kind": start["device_kind"],
            "count": start["device_count"], "memory_peak_bytes": peak,
        }
        result = {**verdict, "metrics": metrics, "device": device}
        if args.trace and ctx.summary:
            device["busy_s"] = ctx.summary["busy_s"]
            device["window_s"] = ctx.summary["window_s"]
            result["breakdown"] = {
                "device_ops": ctx.summary["device_ops"],
                "idle_gaps": ctx.summary["idle_gaps"],
            }
        return result
    except RunFailure:
        logs = sorted(glob.glob(os.path.join(work, "logs", "*.log")))
        for path in [os.path.join(work, "launcher.log"), *logs]:
            _log(f"--- {path} ---\n{_tail(path)}")
        raise
    finally:
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            shutil.copytree(
                work, args.keep, dirs_exist_ok=True,
                ignore=shutil.ignore_patterns("ckpt", "trace"),
            )
            if args.trace and os.path.isdir(os.path.join(work, "trace")):
                _keep_trace(work, args.keep)
        shutil.rmtree(work, ignore_errors=True)


def _keep_trace(work: str, keep: str):
    """The raw trace, gzipped, beside the kept records (for reading by
    hand; a trace of a few steps is some megabytes)."""
    import gzip

    found = glob.glob(os.path.join(work, "trace", "**", "*.xplane.pb"),
                      recursive=True)
    for path in found[:1]:
        with open(path, "rb") as src, gzip.open(
            os.path.join(keep, "trace.xplane.pb.gz"), "wb"
        ) as dst:
            shutil.copyfileobj(src, dst)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None,
                        help="length of the measured window "
                        "(default: run_seconds of BENCHMARK.json)")
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", action="store_true",
                        help="CPU, toy widths, same control flow; prints "
                        "no device metric")
    parser.add_argument("--keep", default="",
                        help="copy the run's records and logs here")
    args = parser.parse_args()
    try:
        if args.seconds is None:
            args.seconds = cells.load_benchmark(ROOT)["run_seconds"]
        result = run(args)
    except (RunFailure, cells.CellError) as e:
        _log(f"FAILED: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
