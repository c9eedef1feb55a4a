#!/usr/bin/env python3
"""The quickest proof that the elastic training path still starts on the chip.

    python3 chip_smoke.py                  # on a machine with a TPU
    python3 chip_smoke.py --cpu-rehearsal  # same control flow, toy width, CPU

Drives the main path once through the entry points a user calls: the
launcher CLI (``python -m dlrover_tpu.cli --standalone --network-check``)
boots a master, an agent, the device-check process, the fork server and a
worker, so the chip passes from process to process the way it does in a
real job. The worker is this same file (``--worker``): ``init_training()``
then ``Trainer(...).fit`` on GPT-2-xl at full width (48 layers, d_model
1600, 25 heads, vocab 50257, seq 1024; weights random from a seed).

Legs:

- ``one``   one chip: bf16 params, ``adam8bit``, Pallas flash attention,
  ``dots`` remat, batch 4x1024; a MEMORY snapshot every step and one DISK
  persist; one SIGKILL of the worker's process group mid-run; the agent
  flushes the snapshot, restarts the worker, it restores and finishes.
- ``four``  one worker driving four chips: fp32 params, ``optax.adamw``,
  ``ParallelSpec(fsdp=4)``, batch 16x1024; the state must be spread evenly
  and a sharded MEMORY snapshot must restore bit-identically.
- ``procs`` four workers with one chip each (``--nproc_per_node=4``), the
  same ``fsdp=4`` steps across four processes.

With one chip visible only ``one`` runs; with four or more, all three.
This (parent) process never imports JAX: a process that has touched JAX
holds the chip, and the children need it. Every figure printed is a smoke
reading from a single short run, not a benchmark.

The last line of standard output is ``{"ok": true, "device": {...}}`` with
the device as JAX reports it. Without a TPU, or with a failed phase, the
exit code is non-zero and that line is not printed.
"""

import argparse
import glob
import itertools
import json
import os
import resource
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
LEGS = ("one", "four", "procs")
# Steps, the step whose end the worker is killed at, DISK persist cadence.
STEPS, KILL_AT, PERSIST_EVERY = 8, 4, 6
LEG_TIMEOUT_S = 900


# --------------------------------------------------------------------
# worker side (runs under the agent; owns the chip)
# --------------------------------------------------------------------

def _leg_job(leg: str, rehearsal: bool):
    """(model config, optimizer, ParallelSpec, batch shape) of a leg."""
    import dataclasses

    import jax.numpy as jnp
    import optax

    from dlrover_tpu.accel import ParallelSpec
    from dlrover_tpu.models.gpt import GPTConfig
    from dlrover_tpu.optim.low_bit import adam8bit

    if rehearsal:
        cfg = GPTConfig(
            vocab_size=512, max_seq_len=128, num_layers=2, num_heads=2,
            d_model=64, remat=True,
        )
        block, seq = 64, 128
    else:
        cfg = GPTConfig.gpt2_xl()
        block, seq = 1024, 1024
    cfg = dataclasses.replace(
        cfg, attn_impl="pallas", attn_block_q=block, attn_block_k=block,
        remat_policy="dots",
    )
    if leg == "one":
        cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
        return cfg, adam8bit(2e-4), ParallelSpec(data=1), (4, seq)
    return cfg, optax.adamw(2e-4), ParallelSpec(fsdp=4), (16, seq)


class _Record:
    """Append-only JSON lines shared by every incarnation of every rank."""

    def __init__(self, path: str, **ident):
        self._path = path
        self._ident = ident

    def write(self, event: str, **fields):
        line = json.dumps(
            {"event": event, **self._ident, **fields}, default=str
        )
        with open(self._path, "a") as f:
            f.write(line + "\n")


def _hlo_counts(text: str) -> dict:
    """Kernel and collective instructions of a compiled HLO module. The
    TPU compiler turns a reduce-scatter into a fusion that calls an
    ``all-reduce-scatter`` computation, and parts of an all-gather into
    collective-permutes."""
    def ops(*names):
        return sum(text.count(f" {n}(") for n in names)

    return {
        "mosaic_calls": text.count('custom_call_target="tpu_custom_call"'),
        "all_gather": ops("all-gather", "all-gather-start"),
        "reduce_scatter": ops("reduce-scatter")
        + text.count("calls=%all-reduce-scatter"),
        "all_reduce": ops("all-reduce", "all-reduce-start"),
        "collective_permute": ops(
            "collective-permute", "collective-permute-start"
        ),
    }


def worker_main(args) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu import train as dtrain
    from dlrover_tpu.models.gpt import GPT, loss_fn
    from dlrover_tpu.train.checkpoint import StorageType
    from dlrover_tpu.train.trainer import Trainer, TrainerCallback

    # Compile accounting straight from JAX: requests that missed the
    # persistent cache, and seconds spent in the backend compiler.
    compiles = {"cache_hits": 0, "cache_misses": 0, "backend_compile_s": 0.0}

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            compiles["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            compiles["cache_misses"] += 1

    def on_duration(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles["backend_compile_s"] += secs

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    dtrain.init_training()
    incarnation = dtrain.restart_count()
    rec = _Record(
        os.path.join(args.out, "worker.jsonl"),
        rank=dtrain.global_rank(), incarnation=incarnation,
    )
    dev = jax.devices()[0]
    fsize = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    rec.write(
        "start", platform=dev.platform, device_kind=dev.device_kind,
        file_size_limit=None if fsize == resource.RLIM_INFINITY else fsize,
        device_count=len(jax.devices()),
        local_device_count=len(jax.local_devices()),
        process_count=jax.process_count(),
        cache_dir=jax.config.jax_compilation_cache_dir,
        **dtrain.bootstrap_timings(),
    )
    if dev.platform != ("cpu" if args.cpu_rehearsal else "tpu"):
        rec.write("fatal", error=f"unexpected platform {dev.platform}")
        return 1

    cfg, opt, spec, batch_shape = _leg_job(args.worker, args.cpu_rehearsal)
    model = GPT(cfg)
    batch = np.random.default_rng(0).integers(
        0, cfg.vocab_size, batch_shape, dtype=np.int32
    )

    def token_loss(module, params, b):
        return loss_fn(module.apply({"params": params}, b), b)

    kill_at = KILL_AT if args.worker == "one" and incarnation == 0 else 0

    class Smoke(TrainerCallback):
        t_prev = None

        def on_train_begin(self, trainer, start_step):
            stats = trainer.checkpointer.engine.last_restore_stats
            rec.write("resume", step=start_step, restore=stats)
            self.t_prev = time.perf_counter()

        def on_step_end(self, trainer, step, metrics):
            jax.block_until_ready(metrics["loss"])
            now = time.perf_counter()
            engine = trainer.checkpointer.engine
            rec.write(
                "step", step=step, loss=float(metrics["loss"]),
                wall_s=round(now - self.t_prev, 3),
                staged_step=engine.cached_step,
                cache_misses=compiles["cache_misses"],
            )
            if step == kill_at:
                # Land this step's snapshot first, so the restart must
                # resume exactly here (per-step snapshots are skipped
                # while an earlier one is still staging).
                engine.wait_staged()
                if engine.cached_step != step:
                    trainer.checkpointer.save_checkpoint(
                        step, trainer.state, StorageType.MEMORY
                    )
                    engine.wait_staged()
                rec.write("kill", step=step, staged_step=engine.cached_step)
                os.killpg(os.getpgrp(), signal.SIGKILL)
            self.t_prev = time.perf_counter()

    t0 = time.perf_counter()
    trainer = Trainer(
        model, opt, token_loss, batch, spec=spec,
        checkpoint_dir=os.path.join(args.out, "ckpt"),
        persist_every=PERSIST_EVERY if args.worker == "one" else 0,
        callbacks=[Smoke()],
    )
    jax.block_until_ready(trainer.state)
    init_s = time.perf_counter() - t0
    state_bytes = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(trainer.state)
    )

    # Compile the step ahead of fit(): a clean compile time, the compiled
    # program's own account of kernels, collectives and memory, and a
    # persistent-cache entry that fit()'s first call must then hit.
    t0 = time.perf_counter()
    lowered = trainer.train_step.lower(
        trainer.state, jax.device_put(batch, trainer.batch_sharding)
    )
    lower_s = time.perf_counter() - t0
    before = dict(compiles)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    rec.write(
        "compiled", init_s=round(init_s, 2), lower_s=round(lower_s, 2),
        compile_s=round(compile_s, 2),
        step_cache_hit=compiles["cache_hits"] > before["cache_hits"],
        state_gb=round(state_bytes / 1e9, 3),
        argument_gb=round(mem.argument_size_in_bytes / 1e9, 3),
        temp_gb=round(mem.temp_size_in_bytes / 1e9, 3),
        **_hlo_counts(compiled.as_text()),
    )
    del lowered, compiled

    out = trainer.fit(itertools.repeat(batch), steps=STEPS)
    engine = trainer.checkpointer.engine
    engine.wait_staged()

    final = {"step": out["step"], "loss": out["loss"]}
    # Read before the restore check below puts a second copy on the chips.
    mem_stats = [d.memory_stats() or {} for d in jax.local_devices()]
    if args.worker == "four":
        # Sharded MEMORY snapshot of the final state, restored in place
        # and compared bit for bit. (Not in ``procs``: the engine's
        # cross-process step vote is held once per incarnation.)
        if engine.cached_step != out["step"]:
            trainer.checkpointer.save_checkpoint(
                out["step"], trainer.state, StorageType.MEMORY
            )
            engine.wait_staged()
        step, restored = trainer.checkpointer.load_checkpoint(trainer.state)
        same = all(
            bool(jnp.array_equal(a, b)) for a, b in zip(
                jax.tree_util.tree_leaves(trainer.state),
                jax.tree_util.tree_leaves(restored),
            )
        )
        final["snapshot_restore"] = {
            "step": step, "bit_identical": same,
            "stats": engine.last_restore_stats,
        }
    if args.worker != "one":
        # Each device must hold its quarter of every sharded leaf.
        wte = trainer.state["params"]["wte"]["embedding"]
        final["wte_shard_shapes"] = [
            list(s.data.shape) for s in wte.addressable_shards
        ]
        final["wte_shape"] = list(wte.shape)
    cache_files = glob.glob(
        os.path.join(jax.config.jax_compilation_cache_dir, "*")
    )
    final.update(
        staging_memory_kind=engine.staging_memory_kind,
        staged_step=engine.cached_step,
        bytes_in_use=[m.get("bytes_in_use") for m in mem_stats],
        peak_bytes_in_use=[m.get("peak_bytes_in_use") for m in mem_stats],
        bytes_limit=[m.get("bytes_limit") for m in mem_stats],
        cache_entries=len(cache_files),
        cache_largest_bytes=max(map(os.path.getsize, cache_files), default=0),
        **compiles,
    )
    rec.write("done", **final)
    trainer.close()
    return 0


# --------------------------------------------------------------------
# parent side (never imports JAX)
# --------------------------------------------------------------------

class SmokeFailure(Exception):
    pass


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _log(msg):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def _tail(path: str, n: int = 60) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(line[:400] for line in f.readlines()[-n:])
    except OSError:
        return ""


def probe_devices(env) -> dict:
    """What JAX sees, asked of a child that exits (and frees the chip)."""
    code = (
        "import json, jax; d = jax.devices(); print(json.dumps({"
        "'platform': d[0].platform, 'kind': d[0].device_kind, "
        "'count': len(d)}))"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=300,
    )
    if r.returncode != 0:
        raise SmokeFailure(f"JAX found no device:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def _kill_job(job: str):
    """SIGKILL whatever still carries this job's name in its environment
    (the master, the fork server and the workers run in sessions of their
    own, so the launcher's process group does not cover them)."""
    needle = f"DLROVER_TPU_JOB_NAME={job}".encode()
    for path in glob.glob("/proc/[0-9]*/environ"):
        pid = int(path.split("/")[2])
        if pid == os.getpid():
            continue
        try:
            with open(path, "rb") as f:
                if needle not in f.read().split(b"\0"):
                    continue
            os.kill(pid, signal.SIGKILL)
        except (OSError, ValueError):
            continue


def run_leg(leg: str, args, env, work: str) -> dict:
    out = os.path.join(work, leg)
    os.makedirs(out)
    job = f"chip-smoke-{leg}-{os.getpid()}"
    leg_env = dict(
        env,
        DLROVER_TPU_JOB_NAME=job,
        DLROVER_TPU_GOODPUT_JSON=os.path.join(out, "goodput.json"),
    )
    if args.cpu_rehearsal:
        devices = {"one": 1, "four": 4, "procs": 1}[leg]
        leg_env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}"
        )
    cmd = [
        sys.executable, "-m", "dlrover_tpu.cli", "--standalone",
        f"--nproc_per_node={4 if leg == 'procs' else 1}",
        "--network-check", "--max_restarts=1", "--monitor_interval=0.5",
        f"--job_name={job}", f"--log_dir={os.path.join(out, 'logs')}",
        os.path.abspath(__file__), "--",
        "--worker", leg, "--out", out,
    ] + (["--cpu-rehearsal"] if args.cpu_rehearsal else [])
    launcher_log = os.path.join(out, "launcher.log")
    t0 = time.monotonic()
    try:
        with open(launcher_log, "wb") as log:
            proc = subprocess.Popen(
                cmd, env=leg_env, cwd=REPO, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
            try:
                rc = proc.wait(timeout=LEG_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                rc = None
    finally:
        _kill_job(job)
        for path in glob.glob(f"/dev/shm/*{job}*"):
            os.unlink(path)
        if not args.cpu_rehearsal:
            # What is too long for the end of the output: logs and
            # records (not the checkpoint) go where the chip tool brings
            # them back.
            shutil.copytree(
                out, os.path.join(REPO, "chiprun_out", "chip_smoke", leg),
                ignore=shutil.ignore_patterns("ckpt"), dirs_exist_ok=True,
            )
    wall = time.monotonic() - t0
    try:
        records = _read_jsonl(os.path.join(out, "worker.jsonl"))
        _check(rc is not None, f"launcher timed out after {LEG_TIMEOUT_S}s")
        _check(rc == 0, f"launcher exited {rc}")
        result = check_leg(leg, records, out, args.cpu_rehearsal)
    except SmokeFailure as e:
        logs = sorted(glob.glob(os.path.join(out, "logs", "*.log")))
        tails = "".join(
            f"\n--- {p} ---\n{_tail(p)}" for p in [launcher_log, *logs]
        )
        raise SmokeFailure(f"leg {leg}: {e}{tails}") from None
    result["wall_s"] = round(wall, 1)
    return result


def _read_jsonl(path: str) -> list:
    try:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]
    except OSError:
        return []


def check_leg(leg: str, records: list, out: str, rehearsal: bool) -> dict:
    """Every assertion of a leg, over what its workers recorded."""
    def of(event, **match):
        return [
            r for r in records if r["event"] == event
            and all(r.get(k) == v for k, v in match.items())
        ]

    fatal = of("fatal")
    _check(not fatal, f"worker refused to run: {fatal}")
    nproc = 4 if leg == "procs" else 1
    chips = 1 if leg == "one" else 4
    platform = "cpu" if rehearsal else "tpu"
    starts = of("start", incarnation=0)
    _check(len(starts) == nproc, f"{len(starts)} workers started")
    for s in starts:
        _check(s["platform"] == platform, f"platform {s['platform']}")
        _check(s["process_count"] == nproc, f"process_count {s}")
        if leg == "procs":
            _check(s["local_device_count"] == 1 and s["device_count"] == 4,
                   f"a worker does not see one chip of four: {s}")
        elif leg == "four":
            _check(s["device_count"] >= chips, f"device_count {s}")
    done = of("done")
    _check(len(done) == nproc, f"{len(done)} of {nproc} workers finished")
    _check(all(d["step"] == STEPS for d in done), "a worker stopped early")

    # Loss: finite, and falling on the repeated batch.
    steps = {
        (r["rank"], r["incarnation"], r["step"]): r for r in of("step")
    }
    for r in steps.values():
        _check(r["loss"] == r["loss"] and abs(r["loss"]) < 1e4,
               f"non-finite loss {r}")
    last_inc = max(r["incarnation"] for r in records)
    losses = [
        steps[(0, inc, s)]["loss"]
        for inc in range(last_inc + 1) for s in range(1, STEPS + 1)
        if (0, inc, s) in steps
    ]
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    compiled = {r["incarnation"]: r for r in of("compiled", rank=0)}
    cold = compiled[0]
    # Compiles of the first incarnation that missed the persistent cache
    # (0 when an earlier run left its entries in the same directory).
    cold["cache_misses"] = max(
        r["cache_misses"] for r in of("step", rank=0, incarnation=0)
    )
    if not rehearsal:
        _check(cold["mosaic_calls"] > 0,
               "no Mosaic call in the compiled step: the Pallas kernels "
               "did not run as kernels")
    if chips > 1:
        # (XLA's CPU pipeline leaves the gradient reduction an all-reduce.)
        reduce = "all_reduce" if rehearsal else "reduce_scatter"
        _check(cold["all_gather"] > 0 and cold[reduce] > 0,
               f"fsdp step without all-gather/{reduce}: {cold}")
    d0 = [d for d in done if d["rank"] == 0][0]
    _check(d0["staging_memory_kind"] == "pinned_host",
           f"snapshot staged through {d0['staging_memory_kind']!r}")

    goodput = {}
    try:
        with open(os.path.join(out, "goodput.json")) as f:
            goodput = json.load(f).get("summary", {})
    except (OSError, ValueError):
        pass
    _check(goodput.get("steps_reported", 0) > 0
           and goodput.get("last_step") == STEPS,
           f"the master did not see the step reports: {goodput}")

    result = {
        "leg": leg, "platform": platform,
        "device_kind": starts[0]["device_kind"],
        "device_count": starts[0]["device_count"],
        "process_count": nproc,
        "losses": [round(x, 4) for x in losses],
        "step_wall_s": [
            steps[(0, last_inc, s)]["wall_s"]
            for s in range(1, STEPS + 1) if (0, last_inc, s) in steps
        ],
        "cold": {
            k: cold[k] for k in (
                "init_s", "lower_s", "compile_s", "state_gb",
                "argument_gb", "temp_gb", "mosaic_calls", "all_gather",
                "reduce_scatter", "all_reduce", "collective_permute",
                "cache_misses",
            )
        },
        "staging_memory_kind": d0["staging_memory_kind"],
        "peak_hbm_gb": [
            round(b / 1e9, 2) if b else None
            for d in sorted(done, key=lambda d: d["rank"])
            for b in d["peak_bytes_in_use"]
        ],
        "cache_dir": starts[0]["cache_dir"],
        "cache_entries": d0["cache_entries"],
        "cache_largest_mb": round(d0["cache_largest_bytes"] / 1e6, 1),
        # Under a limit the snapshot segment and the persisted shard are
        # kept as part files below it (dlrover_tpu/common/fsutil.py).
        "file_size_limit": starts[0]["file_size_limit"],
        "master_steps_reported": goodput.get("steps_reported"),
    }
    if not rehearsal:
        _check(None not in result["peak_hbm_gb"],
               "no peak_bytes_in_use reported")

    if leg == "one":
        kills = of("kill")
        _check(len(kills) == 1 and kills[0]["step"] == KILL_AT,
               f"expected one kill at step {KILL_AT}: {kills}")
        _check(kills[0]["staged_step"] == KILL_AT,
               f"the kill step's snapshot never landed: {kills[0]}")
        resumed = of("resume", incarnation=1)
        _check(len(resumed) == 1, "the killed worker was not restarted")
        _check(resumed[0]["step"] == KILL_AT,
               f"resumed at step {resumed[0]['step']}, killed at {KILL_AT}")
        # The loss continues: the restart's first step must not be a
        # fresh model's, it must carry on below where the run began.
        first_after = steps[(0, 1, KILL_AT + 1)]["loss"]
        _check(first_after < steps[(0, 0, 1)]["loss"],
               f"loss restarted instead of continuing: {losses}")
        warm = compiled[1]
        warm_done = [d for d in done if d["incarnation"] == 1][0]
        _check(warm["step_cache_hit"] and warm_done["cache_misses"] == 0,
               f"the restart compiled anew: {warm} {warm_done}")
        incident = (goodput.get("incidents") or [{}])[0]
        result.update(
            killed_at_step=KILL_AT, resumed_at_step=resumed[0]["step"],
            restore=resumed[0]["restore"],
            restart={
                "compile_s": warm["compile_s"], "init_s": warm["init_s"],
                "first_step_s": steps[(0, 1, KILL_AT + 1)]["wall_s"],
                "cache_hits": warm_done["cache_hits"],
                "cache_misses": warm_done["cache_misses"],
                "kill_to_first_step_s": incident.get("recover_s"),
            },
        )
    else:
        for d in done:
            quarter = d["wte_shape"][0] * d["wte_shape"][1] // 4
            for shape in d["wte_shard_shapes"]:
                _check(shape[0] * shape[1] == quarter,
                       f"a device holds {shape} of wte {d['wte_shape']}")
        if leg == "four":
            snap = d0["snapshot_restore"]
            _check(snap["step"] == STEPS and snap["bit_identical"],
                   f"sharded snapshot did not restore: {snap}")
            result["snapshot_restore"] = snap
        in_use = [b for d in done for b in d["bytes_in_use"] if b]
        if not rehearsal:
            _check(len(in_use) == 4, f"bytes_in_use of {len(in_use)} chips")
            _check(min(in_use) > 1e9 and max(in_use) < 1.2 * min(in_use),
                   f"state not spread evenly: bytes_in_use {in_use}")
        result["bytes_in_use_gb"] = [round(b / 1e9, 2) for b in in_use]
    return result


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--legs", default="",
                        help="comma-separated subset of one,four,procs "
                        "(default: what the visible chips allow)")
    parser.add_argument("--cpu-rehearsal", action="store_true",
                        help="run the same control flow on the CPU at a toy "
                        "width; prints no chip result")
    parser.add_argument("--keep", action="store_true",
                        help="keep the work directory")
    parser.add_argument("--worker", default="", help=argparse.SUPPRESS)
    parser.add_argument("--out", default="", help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.worker:
        return worker_main(args)

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO, env.get("PYTHONPATH", "")) if p
    )
    if args.cpu_rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    results = []
    try:
        if not os.path.isdir(os.path.join(REPO, "dlrover_tpu")):
            raise SmokeFailure("dlrover_tpu/ is not beside chip_smoke.py")
        device = probe_devices(env)
        _log(f"JAX sees {device}")
        if not args.cpu_rehearsal:
            _check(device["platform"] == "tpu",
                   f"no TPU: JAX reports platform {device['platform']!r}")
        legs = [x for x in args.legs.split(",") if x] or (
            list(LEGS) if device["count"] >= 4 or args.cpu_rehearsal
            else ["one"]
        )
        _check(set(legs) <= set(LEGS), f"unknown leg in {legs}")
        for leg in legs:
            _log(f"leg {leg} ...")
            result = run_leg(leg, args, env, work)
            results.append(result)
            print(json.dumps(result), flush=True)
    except SmokeFailure as e:
        _log(f"FAILED: {e}")
        return 1
    finally:
        if results and not args.cpu_rehearsal:
            report = os.path.join(REPO, "chiprun_out", "chip_smoke.json")
            with open(report, "w") as f:
                json.dump(results, f, indent=1)
        if args.keep:
            _log(f"work directory kept at {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)
    if args.cpu_rehearsal:
        print(json.dumps({"rehearsal": "cpu", "passed": True}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
