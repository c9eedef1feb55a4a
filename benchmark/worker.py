"""The training script of a benchmark cell: what a user's job would be.

Started by ``python -m dlrover_tpu.cli`` under the agent, so this process
(and after a kill, its successor) owns the chip. It calls
``init_training()``, builds ``Trainer`` and runs ``Trainer.fit`` over an
``ElasticDataLoader``; everything it measures, it measures from its own
callback and wrappers, and writes to ``worker.jsonl`` for the parent.

Incarnation 0: set-up (weights from the seed, the reference comparison),
warm-up steps, the measured window, then either stop or, in a job with
``kill``, train on until the next snapshot has landed and SIGKILL its own
process group. Incarnation 1: restore, fingerprint, three steps, stop.
"""

import argparse
import contextlib
import importlib
import itertools
import json
import os
import resource
import signal
import sys
import threading
import time

T_ENTRY = time.time()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from dlrover_tpu.train.data.device_prefetch import (  # noqa: E402
    DevicePrefetchIterator,
)
from dlrover_tpu.train.trainer import Trainer, TrainerCallback  # noqa: E402


class Compiles:
    """Programs compiled or loaded, straight from ``jax.monitoring``."""

    def __init__(self):
        self.requests = self.cache_hits = self.cache_misses = 0
        self.backend_compile_s = 0.0
        self.programs = []          # (function name, seconds), in order
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _duration(self, name, secs, fun_name="", **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.backend_compile_s += secs
            self.programs.append((str(fun_name), round(secs, 3)))

    def snapshot(self) -> dict:
        return {
            "compile_requests": self.requests, "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "backend_compile_s": self.backend_compile_s,
        }


def make_optimizer(spec: dict):
    """``{"factory": "package.module:function", "args": {...}}``."""
    module, _, name = spec["factory"].partition(":")
    return getattr(importlib.import_module(module), name)(**spec["args"])


@jax.jit
def fingerprint(state):
    """Two wrapping 32-bit sums over the bits of every leaf, computed on
    the device: equal states give equal lists."""
    jnp = jax.numpy
    unsigned = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}
    sums = []
    for x in jax.tree_util.tree_leaves(state):
        u = jax.lax.bitcast_convert_type(
            x, unsigned[x.dtype.itemsize]
        ).astype(jnp.uint32)
        sums += [jnp.sum(u, dtype=jnp.uint32),
                 jnp.sum(u * u, dtype=jnp.uint32)]
    return jnp.stack(sums)


def check_against_reference(cell, family, built, trainer, spec, first, rec):
    """The system's loss and gradients against the plain reference, on the
    first batch and the system's own initial parameters (``correct`` (a)
    and (b)); recorded, judged by the parent."""
    import flax.linen as nn

    from benchmark import cells, compare
    from benchmark.reference import common

    t0 = time.perf_counter()
    config, job = cell["config"], cell["job"]
    reference = cells.family_module(
        "reference", cell["family"], cell["bench_dir"]
    )
    module, loss = built["module"], built["loss"]
    params = trainer.state["params"]
    mesh = trainer.batch_sharding.mesh
    rules = list(spec.rules(vocab_size=config["vocab_size"]))
    # The gradient sample: the first sequences of the first batch, cut to
    # what the reference can hold beside the training state.
    sample_of = job.get("reference", {})
    n = int(sample_of.get("grad_sample_sequences", 1))
    length = int(sample_of.get("grad_sample_tokens", first.shape[1]))
    shards = mesh.devices.size

    def place(tokens):
        if tokens.shape[0] % shards == 0:
            return jax.device_put(tokens, trainer.batch_sharding)
        return tokens

    sample = place(first[:n, :length])
    per_seq, ref_grads = jax.jit(
        lambda p, t: common.loss_and_grads(
            reference, family.to_reference(p), t, config
        )
    )(params, sample)
    if sample.shape != first.shape:
        per_seq_batch = jax.jit(
            lambda p, t: common.losses(
                reference, family.to_reference(p), t, config
            )
        )(params, place(first))
    else:
        per_seq_batch = per_seq

    def system(p, t):
        with mesh, nn.logical_axis_rules(rules):
            return jax.value_and_grad(lambda q: loss(module, q, t))(p)

    sys_loss, sys_grads = jax.jit(system)(params, sample)
    agreement = compare.agreement(family.to_reference(sys_grads), ref_grads)
    rec.write(
        "reference", sample_shape=list(sample.shape),
        loss_ref_batch=float(per_seq_batch.mean()),
        loss_ref_sample=float(per_seq.mean()),
        loss_sys_sample=float(sys_loss), agreement=agreement,
        tolerance=(
            common.TOY_TOLERANCE if cell["toy"] else reference.TOLERANCE
        ),
        seconds=time.perf_counter() - t0,
    )


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cell", required=True,
                        help="the resolved cell, written by run.py")
    args = parser.parse_args()
    with open(args.cell) as f:
        cell = json.load(f)
    config, job, out = cell["config"], cell["job"], cell["out"]

    from benchmark import cells, costs, records, traffic
    from dlrover_tpu import train as dtrain
    from dlrover_tpu.accel import ParallelSpec
    from dlrover_tpu.train.data import ElasticDataLoader, ElasticSampler

    compiles = Compiles()
    dtrain.init_training()
    incarnation = dtrain.restart_count()
    rec = records.Record(
        os.path.join(out, "worker.jsonl"), incarnation=incarnation
    )
    devices = jax.devices()
    fsize = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    rec.write(
        "start", t_entry=T_ENTRY, platform=devices[0].platform,
        device_kind=devices[0].device_kind, device_count=len(devices),
        cache_dir=jax.config.jax_compilation_cache_dir,
        file_size_limit=None if fsize == resource.RLIM_INFINITY else fsize,
        **dtrain.bootstrap_timings(),
    )
    want = "cpu" if cell["rehearsal"] else "tpu"
    if devices[0].platform != want or len(devices) < cell["chips"]:
        rec.write("fatal", error=(
            f"the cell needs {cell['chips']} {want} device(s); JAX reports "
            f"{len(devices)} x {devices[0].platform}"
        ))
        return 1
    if not cell["rehearsal"]:
        costs.load_peaks(devices[0].device_kind)  # unknown kind: an error

    # ---- the job ----------------------------------------------------
    t0 = time.perf_counter()
    family = cells.family_module("models", cell["family"], cell["bench_dir"])
    built = family.build(config, job)
    batch_size, seq = int(job["batch"]), int(job["sequence"])
    tokens_per_step = batch_size * seq
    dataset = traffic.make_dataset(
        job["data"], seq, config["vocab_size"], cell["seed"]
    )
    sampler = ElasticSampler(
        len(dataset), shuffle=True, seed=cell["seed"], drop_last=True
    )
    loader = ElasticDataLoader(
        traffic.TokenDataset(dataset), batch_size=batch_size,
        sampler=sampler, drop_last=True, prefetch=2,
    )
    spec = ParallelSpec(**job["parallel"])
    checkpointing = bool(job["checkpoint"]["enabled"])
    window = Window(rec, cell, compiles, tokens_per_step, incarnation)
    trainer = Trainer(
        built["module"], make_optimizer(job["optimizer"]), built["loss"],
        dataset[:batch_size], spec=spec,
        checkpoint_dir=os.path.join(out, "ckpt") if checkpointing else "",
        persist_every=int(job["checkpoint"].get("persist_every", 0)),
        callbacks=[window], rng=jax.random.PRNGKey(cell["seed"]),
    )
    jax.block_until_ready(trainer.state)
    # Trace the step now, from this one line, in every incarnation. A
    # Mosaic kernel's body is serialized into the step's module with the
    # Python locations of its trace, and what was traced before it in the
    # process (here: the reference comparison, in incarnation 0 only)
    # moves them. The module is the compile cache's key, so a restart
    # that traced in another order would miss the cache and compile the
    # step anew. fit()'s first call finds this trace and does not repeat it.
    trainer.train_step.trace(trainer.state, jax.ShapeDtypeStruct(
        (batch_size, seq), dataset.dtype, sharding=trainer.batch_sharding
    ))
    rec.write("built", build_s=time.perf_counter() - t0,
              **compiles.snapshot())

    # ---- resume, or the reference comparison ---------------------------
    t0 = time.perf_counter()
    start = trainer.restore()
    engine = trainer.checkpointer.engine if checkpointing else None
    if incarnation > 0:
        rec.write(
            "resume", step=start, restore_s=time.perf_counter() - t0,
            restore=engine.last_restore_stats if engine else None,
            fingerprint=np.asarray(fingerprint(trainer.state)).tolist(),
        )
    batches = traffic.epochs(loader, sampler, batch_size, start)
    if incarnation == 0:
        first = next(batches)
        check_against_reference(
            cell, family, built, trainer, spec, first, rec
        )
        batches = itertools.chain([first], batches)

    # ---- the loop ---------------------------------------------------
    window.attach(trainer, engine)
    feed = TimedPrefetch(window, batches, trainer.batch_sharding)
    steps = start + 3 if incarnation > 0 else 10 ** 9
    result = trainer.fit(feed, steps=steps, start_step=start)
    window.finish(result)
    trainer.close()
    return 0


class TimedPrefetch(DevicePrefetchIterator):
    """``next(batch)`` as the trainer's loop sees it, timed (and, in the
    traced run, annotated)."""

    def __init__(self, window, batches, sharding, depth: int = 2):
        self._window = window
        super().__init__(batches, sharding, depth=depth)

    def __next__(self):
        t0 = time.perf_counter()
        with self._window.span("bench.next_batch"):
            batch = super().__next__()
        self._window.input_wait.append(time.perf_counter() - t0)
        return batch


class Window(TrainerCallback):
    """Stamps step ends, opens and closes the measured window, runs the
    trace, watches snapshots land, kills, and records all of it.

    The window opens after the job's warm-up steps. A job's ``window``
    says what closes it. ``"seconds"``: the first step end ``--seconds``
    later, the device drained. ``"snapshot_cycles"`` (a job with a
    checkpoint directory, where a snapshot is always in flight and each
    costs the loop seconds at its dispatch): the first landing at least
    ``--seconds`` later that is at least the job's ``landings``-th in the
    window, so that it holds whole snapshot cycles; a job with ``kill``
    SIGKILLs its process group there.
    """

    def __init__(self, rec, cell, compiles, tokens_per_step, incarnation):
        self.rec, self.cell = rec, cell
        self.compiles, self.incarnation = compiles, incarnation
        self.tokens_per_step = tokens_per_step
        job = cell["job"]
        self.warmup = int(job.get("warmup_steps", 3))
        self.trace_steps = int(job.get("trace_steps", 6))
        self.cycles = job.get("window", "seconds") == "snapshot_cycles"
        self.kill = bool(job.get("kill")) and incarnation == 0
        if (self.cycles or self.kill) and not (
            self.cycles and job["checkpoint"]["enabled"]
        ):
            raise ValueError(
                "kill needs a snapshot_cycles window, and that a "
                "checkpoint directory"
            )
        self.min_landings = int(job.get("landings", 1))
        self.landings_in_window = 0
        self.deadline = float(job.get("window_deadline_s", 150))
        self.seconds = float(cell["seconds"])
        self.tracing = bool(cell["trace"]) and incarnation == 0
        self.trace_state = "off"       # off -> on -> done
        self.trace_until = 0
        self.stamps = []               # (step, perf, wall, loss_lag1)
        self.input_wait, self.save_call = [], []
        self.dispatched = []           # (step, wall) snapshots taken
        self.landed = []               # (step, wall) snapshots restorable
        self.fingerprints = {}
        self.t_open = self.t_close = None          # perf_counter
        self.t_open_wall = self.t_close_wall = None
        self.open_compiles = self.close_compiles = None
        self.first_step_done = False
        self.trainer = self.engine = None
        self._lock = threading.Lock()

    # ---- wiring -----------------------------------------------------
    def attach(self, trainer, engine):
        self.trainer, self.engine = trainer, engine
        if engine is None:
            return
        save = trainer.checkpointer.save_checkpoint

        def timed_save(step, state, *a, **kw):
            t0 = time.perf_counter()
            with self.span("bench.save_checkpoint"):
                taken = save(step, state, *a, **kw)
            self.save_call.append(time.perf_counter() - t0)
            if taken:
                self.dispatched.append((step, time.time()))
                if self.kill:
                    # Dispatched after the step that made this state and
                    # before the step that donates it.
                    self.fingerprints[step] = fingerprint(state)
            return taken

        trainer.checkpointer.save_checkpoint = timed_save
        threading.Thread(
            target=self._watch_landings, daemon=True, name="bench-landings"
        ).start()

    def span(self, name):
        if self.trace_state == "on":
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def _open(self):
        self.t_open, self.t_open_wall = time.perf_counter(), time.time()
        self.open_compiles = self.compiles.snapshot()

    def _close(self):
        self.t_close, self.t_close_wall = time.perf_counter(), time.time()
        self.close_compiles = self.compiles.snapshot()

    # ---- snapshots --------------------------------------------------
    def _watch_landings(self):
        last = self.engine.cached_step
        while True:
            step = self.engine.cached_step
            if step != last:
                last = step
                self.landed.append((step, time.time()))
                if self.cycles and self.incarnation == 0:
                    self._on_landing(step)
            time.sleep(0.005)

    def _on_landing(self, step):
        if self.t_open is None or self.t_close is not None:
            return
        self.landings_in_window += 1
        if self.landings_in_window >= self.min_landings and (
            time.perf_counter() - self.t_open >= self.seconds
        ):
            self._close()
            self._stop_trace()      # a trace still running is written out
            if self.kill:
                self._kill(step)
            self.trainer.should_stop = True

    def _kill(self, snapshot_step):
        fp = self.fingerprints.get(snapshot_step)
        fp = None if fp is None else np.asarray(fp).tolist()
        self._flush("kill", snapshot_step=snapshot_step, fingerprint=fp,
                    t_kill=time.time())
        os.killpg(os.getpgrp(), signal.SIGKILL)

    # ---- the trainer's hooks ------------------------------------------
    def on_step_end(self, trainer, step, metrics):
        with self.span("bench.on_step_end"):
            self._on_step_end(trainer, step, metrics)

    def _on_step_end(self, trainer, step, metrics):
        if self.incarnation > 0 and not self.first_step_done:
            # resume_s ends here: the first new step, finished.
            jax.block_until_ready(metrics["loss"])
            self.first_step_done = True
            self.rec.write("first_step", t_done=time.time(), step=step,
                           loss=float(metrics["loss"]),
                           **self.compiles.snapshot())
        # The loop has just fenced on the step before this one: its end.
        self.stamps.append(
            (step, time.perf_counter(), time.time(), metrics["loss_lag1"])
        )
        if self.incarnation > 0 or self.t_close is not None:
            return
        if len(self.stamps) == self.warmup:
            self._open()
        if self.t_open is None:
            return
        # The trace starts with the window or, where snapshots cycle, at
        # the window's first landing: a cycle's start, the next dispatch.
        if self.tracing and self.trace_state == "off" and (
            self.landings_in_window or not self.cycles
        ):
            self._start_trace(step)
        elif step >= self.trace_until:
            self._stop_trace(metrics)
        waited = time.perf_counter() - self.t_open
        if not self.cycles and waited >= self.seconds:
            # Drain: the window's last step end is this step's own.
            jax.block_until_ready(metrics["loss"])
            self._stop_trace(metrics)
            self._close()
            self.stamps.append((step + 1, self.t_close, self.t_close_wall,
                                float(metrics["loss"])))
            trainer.should_stop = True
        elif self.cycles and waited > self.seconds + self.deadline:
            self._close()       # the closing landing never came
            trainer.should_stop = True

    # ---- the traced part of the window ---------------------------------
    def _start_trace(self, step):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(
            os.path.join(self.cell["out"], "trace"),
            profiler_options=options,
        )
        self.trace_state = "on"
        self.trace_until = step + self.trace_steps
        self.trace_started_wall = time.time()

    def _stop_trace(self, metrics=None):
        """From the loop (``metrics``: drain first, so the last traced step
        is whole) or from the watcher before a kill, whichever is first."""
        with self._lock:
            if self.trace_state != "on":
                return
            self.trace_state = "done"
            if metrics is not None:
                jax.block_until_ready(metrics["loss"])
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.rec.write("trace", started=self.trace_started_wall,
                           steps=self.trace_steps,
                           stop_s=time.perf_counter() - t0)

    # ---- records ------------------------------------------------------
    def _flush(self, event, **fields):
        """Everything seen so far, in one record (a killed worker writes
        nothing at exit)."""
        from dlrover_tpu.utils.tracing import get_tracer

        with self._lock:
            devices = jax.local_devices()
            self.rec.write(
                event,
                stamps=list(self.stamps),
                t_open=self.t_open, t_close=self.t_close,
                t_open_wall=self.t_open_wall,
                t_close_wall=self.t_close_wall,
                open_compiles=self.open_compiles,
                close_compiles=self.close_compiles,
                tokens_per_step=self.tokens_per_step,
                input_wait=list(self.input_wait),
                save_call=list(self.save_call),
                dispatched=list(self.dispatched), landed=list(self.landed),
                ckpt_io=[
                    {"ts": e["ts"] / 1e6, **e["args"]}
                    for e in get_tracer().events if e["name"] == "ckpt.io"
                ],
                memory=[d.memory_stats() or {} for d in devices],
                programs=list(self.compiles.programs),
                **self.compiles.snapshot(),
                **fields,
            )

    def finish(self, result):
        self._flush("done", final_step=result["step"],
                    final_loss=result["loss"])


if __name__ == "__main__":
    sys.exit(main())
