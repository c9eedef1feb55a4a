"""High-level training orchestration — the AtorchTrainer analog.

Parity: reference ``atorch/atorch/trainer/atorch_trainer.py`` (a
HF-Trainer-shaped loop wiring accelerate, checkpointing, evaluation,
schedulers, callbacks, logging and resume into one object). The TPU
version composes the framework's own pieces — ``auto_accelerate`` (or
``ElasticTrainer`` for grad accum), the flash-checkpoint engines, the
elastic data layer, the profiler and the master metric reports — into
a ``fit()`` loop, so the per-user training script shrinks to model +
loss + data. HF-Trainer-shaped surface:

- **callbacks**: :class:`TrainerCallback` hooks (train begin/end, step
  end, evaluate, save) with a ``trainer.should_stop`` flag for early
  stopping; :class:`LoggingCallback` ships interval logging with
  loss / tokens-per-second / learning rate;
- **evaluation**: ``evaluate()`` runs a jitted forward-only loss over
  an eval stream (no grads, params not donated); ``fit(eval_batches=,
  eval_every=)`` interleaves it and reports ``eval_loss``;
- **LR schedules**: pass any optax schedule inside the optimizer as
  usual; hand the same callable to ``lr_schedule=`` and the trainer
  surfaces the current LR in step metrics/logs (the reference logs
  ``lr_scheduler.get_last_lr()`` the same way).

The loop is crash-safe by construction: MEMORY snapshots every step
(async, ~ms), DISK persists on a cadence, and a restart resumes from
whatever the agent flushed.
"""

import os
import time
from typing import Any, Callable, Iterable, Optional, Sequence

from dlrover_tpu.chaos.injector import fault_hit
from dlrover_tpu.chaos.sites import ChaosSite
from dlrover_tpu.common import env_utils
from dlrover_tpu.common.log import logger
from dlrover_tpu.observability.events import EventKind, emit
from dlrover_tpu.utils.tracing import get_tracer


def _raise_counters(tracer, metrics: dict):
    """What a step counted beside its loss (``accel.make_train_step``),
    raised as the tracer's counters: the metric ``name{label=value,...}``
    is the series ``label=value`` of the counter ``name``, raised by the
    step's value."""
    for key, value in metrics.items():
        if key == "loss":
            continue
        name, _, labels = key.partition("{")
        tracer.count(name, float(value), **dict(
            pair.split("=", 1) for pair in labels.rstrip("}").split(",")
            if pair
        ))


class TrainerCallback:
    """Hook points mirroring the reference's HF-style callbacks. Any
    hook may set ``trainer.should_stop = True`` to end ``fit`` after
    the current step (early stopping, budget exhaustion, ...).

    Metric semantics under the async pipeline (``fit(pipeline=True)``,
    the default — see docs/async_pipeline.md):

    - ``metrics["loss"]`` is this step's loss as a ``jax.Array``.
      Reading it (``float(...)`` or formatting) synchronizes on the
      *current* step — do that only at your own cadence (the built-in
      ``LoggingCallback`` reads it every ``every`` steps), never
      unconditionally, or you serialize the pipeline you paid for.
    - ``metrics["loss_lag1"]`` is the *previous* step's loss as a plain
      float (None on the first step). It is free: the loop already read
      it as its lag-1 pacing fence while the current step ran on
      device. Prefer it for per-step consumers (metric shippers,
      convergence monitors) that don't need this very step's value.
    - ``metrics["step_time_s"]`` is the host wall time between
      consecutive lag-1 fences — in steady state the true device step
      time, not the (microseconds) async-dispatch time.

    With ``pipeline=False`` the loop syncs every step and
    ``metrics["loss"]`` is a plain float (``loss_lag1`` is absent)."""

    def on_train_begin(self, trainer, start_step: int):
        pass

    def on_step_end(self, trainer, step: int, metrics: dict):
        pass

    def on_evaluate(self, trainer, step: int, metrics: dict):
        pass

    def on_save(self, trainer, step: int, storage: str):
        pass

    def on_train_end(self, trainer, step: int):
        pass


class LoggingCallback(TrainerCallback):
    """Interval logging: loss, step time, tokens/s, and the current
    learning rate when the trainer knows the schedule."""

    def __init__(self, every: int = 10):
        self.every = max(1, every)
        self._t0 = None

    def on_step_end(self, trainer, step, metrics):
        if step % self.every:
            return
        parts = [f"step {step}", f"loss {metrics['loss']:.4f}"]
        if "step_time_s" in metrics:
            parts.append(f"{metrics['step_time_s'] * 1e3:.0f} ms/step")
        if "tokens_per_s" in metrics:
            parts.append(f"{metrics['tokens_per_s'] / 1e3:.1f}k tok/s")
        if "lr" in metrics:
            parts.append(f"lr {metrics['lr']:.2e}")
        logger.info("train | %s", " | ".join(parts))

    def on_evaluate(self, trainer, step, metrics):
        logger.info(
            "eval  | step %s | eval_loss %.4f (%s batches)",
            step, metrics["eval_loss"], metrics["eval_batches"],
        )


class Trainer:
    def __init__(
        self,
        model,
        optimizer,
        loss: Callable,                      # (module, params, batch) -> scalar
        sample_batch,
        spec: Any = "auto",
        checkpoint_dir: str = "",
        persist_every: int = 100,
        grad_accum: int = 1,
        profiler=None,
        report_metrics: bool = True,
        callbacks: Sequence[TrainerCallback] = (),
        lr_schedule: Optional[Callable[[int], float]] = None,
        **accel_kwargs,
    ):
        import jax

        from dlrover_tpu.accel import auto_accelerate

        self._result = auto_accelerate(
            model, optimizer, sample_batch, loss, spec=spec,
            grad_accum=grad_accum, **accel_kwargs,
        )
        self.state = self._result.state
        self._loss = loss
        self._callbacks = list(callbacks)
        self._lr_schedule = lr_schedule
        self._eval_step = None
        self.should_stop = False
        self._persist_every = persist_every
        self._profiler = profiler
        self._report = report_metrics
        self._ckpt = None
        if checkpoint_dir:
            from dlrover_tpu.train.checkpoint import (
                FlashCheckpointer,
                ShardedCheckpointer,
            )

            cls = (
                ShardedCheckpointer if jax.process_count() > 1
                else FlashCheckpointer
            )
            from dlrover_tpu.accel.zero import zero_degree_of

            # Stamp the ZeRO degree into every ShardMeta so a restore
            # under a different data degree fails naming both degrees
            # instead of loading a wrong optimizer slice.
            self._ckpt = cls(
                checkpoint_dir,
                zero_degree=zero_degree_of(self._result.spec),
            )
        self._client = None
        if report_metrics and env_utils.MASTER_ADDR.get():
            from dlrover_tpu.agent.master_client import MasterClient

            self._client = MasterClient.singleton_instance()
        from dlrover_tpu.train.elastic_trainer import StepProgressReporter

        self._progress = StepProgressReporter(
            every=env_utils.PROGRESS_EVERY.get()
        )
        # Per-step phase breakdown (host-input / compute / collective /
        # readback) feeding the master's straggler detector, from the
        # durations of the loop's own spans (trainer.input, .dispatch,
        # .fence, .readback) around fences the loop takes anyway —
        # never an extra sync on the run-ahead step.
        self._phases = None
        if env_utils.STRAGGLER_PHASES.get():
            from dlrover_tpu.utils.profiler import PhaseBreakdown

            self._phases = PhaseBreakdown()
        self._phase_every = max(1, env_utils.STRAGGLER_PHASE_EVERY.get())

    @property
    def phase_breakdown(self):
        """The live :class:`~dlrover_tpu.utils.profiler.PhaseBreakdown`
        (None when DLROVER_TPU_STRAGGLER_PHASES is off)."""
        return self._phases

    @property
    def checkpointer(self):
        """The flash checkpointer (None without ``checkpoint_dir``)."""
        return self._ckpt

    @property
    def train_step(self):
        return self._result.train_step

    @property
    def batch_sharding(self):
        return self._result.batch_sharding

    def restore(self) -> int:
        """Resume from the newest checkpoint; returns the step to start
        from (0 when fresh)."""
        import jax

        if self._ckpt is None:
            return 0
        tracer = get_tracer()
        with tracer.span("trainer.restore") as span:
            step, restored = self._ckpt.load_checkpoint(self.state)
            span.args["step"] = step
            if step > 0:
                # The freshly initialised state was only the template.
                # Keep no reference to it, or it stays resident beside
                # the restored one — on a memory-filling job that is an
                # OOM on the first step after every restart.
                self._result.state = self.state = None
                # Commit host leaves to the step's shardings now. Left as
                # host arrays they carry different avals than the state
                # the step was traced for, the first step traces anew,
                # and that trace misses the persistent compile cache: a
                # full recompile on every first restart. Dispatched, not
                # awaited: the first step's fence pays the transfer.
                with tracer.span("trainer.restore_put"):
                    self.state = jax.device_put(
                        restored, self._result.shardings
                    )
        if step > 0:
            logger.info("trainer resumed from step %s", step)
        return max(0, step)

    def _fire(self, hook: str, *args):
        for cb in self._callbacks:
            try:
                getattr(cb, hook)(self, *args)
            except Exception:
                logger.exception("trainer callback %s failed", hook)

    def evaluate(self, batches: Iterable,
                 max_batches: int = 0) -> dict:
        """Forward-only loss over an eval stream (params NOT donated):
        returns {'eval_loss': mean, 'eval_batches': n}."""
        import jax

        if self._eval_step is None:
            module = self._result.module
            loss = self._loss
            from dlrover_tpu.accel.accelerate import split_loss

            self._eval_step = jax.jit(
                lambda params, b: split_loss(loss(module, params, b))[0],
                in_shardings=(
                    self._result.shardings["params"],
                    self.batch_sharding,
                ),
            )
        import itertools

        from dlrover_tpu.train.data.device_prefetch import (
            DevicePrefetchIterator,
        )

        # Device-side accumulation + prefetch: one host sync for the
        # whole eval stream instead of one per batch. max_batches is
        # applied on the host side so the prefetcher never consumes
        # batches past the limit from a caller's iterator.
        src = (
            itertools.islice(batches, max_batches) if max_batches
            else batches
        )
        total, n = 0.0, 0
        for batch in DevicePrefetchIterator(
            src, self.batch_sharding, depth=2
        ):
            total = total + self._eval_step(self.state["params"], batch)
            n += 1
        out = {
            "eval_loss": float(total) / max(n, 1),
            "eval_batches": n,
        }
        return out

    def fit(self, batches: Iterable, steps: int,
            start_step: Optional[int] = None,
            eval_batches: Optional[Callable[[], Iterable]] = None,
            eval_every: int = 0,
            eval_max_batches: int = 0,
            pipeline: bool = True,
            prefetch_depth: int = 2,
            rescale_engine=None) -> dict:
        """Run the loop; returns {'step': last, 'loss': last[, 'eval_loss']}.

        ``batches`` yields device-puttable batches; the loop consumes one
        per optimizer step and stops at ``steps``, when data runs out, or
        when a callback sets ``should_stop``. ``eval_batches`` is a
        zero-arg callable returning a fresh eval iterable (evaluated
        every ``eval_every`` steps and once at the end).

        ``pipeline=True`` (default) runs the async step pipeline
        (docs/async_pipeline.md): batches are double-buffered onto the
        device ahead of the step that consumes them
        (:class:`~dlrover_tpu.train.data.DevicePrefetchIterator`,
        ``prefetch_depth`` in flight), the loss stays a ``jax.Array``
        (read back lag-1 as the pacing fence), and the host never
        blocks on the *current* step except at explicit boundaries —
        the logging cadence of a callback that reads ``metrics["loss"]``,
        eval, DISK persists, and the final step. The computed loss
        trajectory is bit-identical to ``pipeline=False``; only when
        values are read back changes (see :class:`TrainerCallback`).
        ``pipeline=False`` is the reference synchronous loop:
        ``device_put`` inside the step context and a full device sync
        per step — the baseline ``tests/test_trainer.py`` compares
        the pipelined loop's losses against.

        ``rescale_engine`` (a
        :class:`~dlrover_tpu.train.rescale.RescaleEngine` whose host
        built this trainer's train step) lets the loop absorb an
        in-place rescale plan mid-fit: at the engine's poll cadence the
        loop checks for a plan, and on a successful transition adopts
        the transferred state, rebuilt step and (when the engine has a
        ``data_factory``) the re-batched data stream without leaving
        ``fit``. Without an engine — or when a transition nacks — the
        legacy restart path applies.
        """
        import contextlib

        import jax

        from dlrover_tpu import train as dtrain
        from dlrover_tpu.train import report_training_metrics
        from dlrover_tpu.train.checkpoint import StorageType
        from dlrover_tpu.train.data.device_prefetch import (
            DevicePrefetchIterator,
        )
        from dlrover_tpu.train.metrics import (
            DeferredMetrics,
            batch_token_count,
        )

        from dlrover_tpu.train.comms import (
            CommsGovernor,
            get_governor,
            install_governor,
        )

        # Hot-path I/O governance: consult the master-published link
        # profile and push checkpoint staging + metric readback off
        # saturated-step windows. Installed process-wide so the
        # checkpoint engine (constructed earlier) finds it lazily.
        if (
            env_utils.COMMS_GOVERNOR.get() and self._client is not None
            and get_governor() is None
        ):
            install_governor(CommsGovernor(client=self._client))
        governor = get_governor()

        start = self.restore() if start_step is None else start_step
        if pipeline:
            it = (
                batches if isinstance(batches, DevicePrefetchIterator)
                else DevicePrefetchIterator(
                    batches, self.batch_sharding, depth=prefetch_depth
                )
            )
        else:
            it = iter(batches)
        deferred = DeferredMetrics()
        last_loss: Any = float("nan")
        last_eval: dict = {}
        evaluated_at = -1
        done = start
        self.should_stop = False  # a previous fit's stop must not leak
        self._fire("on_train_begin", start)
        tracer = get_tracer()
        t_mark = time.perf_counter()
        for step in range(start, steps):
            # One span an iteration, parent of the rest (SPANS in
            # utils/tracing.py has the table); each carries the number
            # of the step it computes, as reports and snapshots do.
            with tracer.span("trainer.step", step=step + 1):
                if rescale_engine is not None:
                    transition = rescale_engine.maybe_rescale(
                        self.state, prefetch=it if pipeline else None
                    )
                    if transition is not None and transition.ok:
                        # Adopt the new world: transferred state, rebuilt
                        # step/shardings; the eval step is lazily rebuilt.
                        self.state = transition.state
                        self._result = transition.result
                        self._eval_step = None
                        if not pipeline and transition.batches is not None:
                            it = iter(transition.batches)
                try:
                    with tracer.span("trainer.input") as input_span:
                        batch = next(it)
                except StopIteration:
                    logger.info("data exhausted at step %s", step)
                    break
                ctx = (
                    self._profiler.step() if self._profiler is not None
                    else contextlib.nullcontext()
                )
                # Host dispatch segment: chaos straggle sleep + device_put
                # + the jitted step's (async) dispatch. An injected
                # host-side straggle lands here, never in the collective
                # estimate.
                with tracer.span("trainer.dispatch") as dispatch:
                    chaos = fault_hit(
                        ChaosSite.TRAINER_STEP, detail=str(step)
                    )
                    if chaos is not None and chaos.kind in (
                        "straggle", "delay"
                    ):
                        # Scripted straggler: the sleep lands inside the
                        # step's wall time, so the slowdown is visible to
                        # the same step-rate reporting the master's speed
                        # monitor reads.
                        time.sleep(chaos.delay_s)  # dtlint: disable=DT003 -- scripted chaos straggle, not a poll
                    with ctx:
                        if not pipeline:
                            batch = jax.device_put(
                                batch, self.batch_sharding
                            )
                        self.state, metrics = self.train_step(
                            self.state, batch
                        )
                        if self._profiler is not None:
                            # Honored only when the profiler runs in sync
                            # mode; otherwise it records async-dispatch
                            # time and says so.
                            self._profiler.fence(metrics["loss"])
                done = step + 1
                if self._ckpt is not None:
                    # DISK persist: an explicit boundary — the engine
                    # fetches the (dispatched) state; the runtime orders
                    # those reads after the step that produced it.
                    # MEMORY snapshot: dispatch-only (~ms). The engine
                    # device_puts engine-owned copies of the new state
                    # *before* this thread dispatches step N+1, so a
                    # later donated step can never invalidate the
                    # snapshot even with the loop running ahead.
                    disk = bool(
                        self._persist_every
                        and done % self._persist_every == 0
                    )
                    with tracer.span("trainer.save"):
                        self._ckpt.save_checkpoint(
                            done, self.state,
                            StorageType.DISK if disk else StorageType.MEMORY,
                        )
                    if disk:
                        self._fire("on_save", done, "disk")
                if self._report:
                    with tracer.span("trainer.report"):
                        if (
                            self._client is not None
                            and dtrain.global_rank() == 0
                        ):
                            try:
                                self._client.report_global_step(
                                    done, time.time()
                                )
                            except Exception:
                                # Step reporting is best-effort but a
                                # broken link should be visible once per
                                # occurrence.
                                logger.debug(
                                    "step report failed", exc_info=True
                                )
                            self._progress.note(done)
                        report_training_metrics(done)
                last_loss = metrics["loss"]
                governed = False
                if pipeline:
                    # Lag-1 fence: block on step N-1 (already finished or
                    # finishing while step N runs), never on step N. This
                    # paces the host to the device rate, which also makes
                    # the inter-fence wall time an honest step time. Under
                    # a saturated link the governor skips the fence AND
                    # the readback for the step (bounded by its defer
                    # cap): the device queue runs ahead instead of
                    # draining its D2H through a congested transfer; the
                    # pending slot is picked up by the next un-governed
                    # step's push.
                    governed = (
                        governor is not None
                        and not governor.allow_readback(done)
                    )
                    # The lag-1 wait, split into the device fence (block
                    # until step N-1's metrics exist) and the host
                    # readback (D2H transfer + float conversion) — the
                    # readback is exactly what a degraded D2H link
                    # inflates. Still lag-1: never a sync on step N.
                    with tracer.span("trainer.fence") as fence:
                        if not governed:
                            deferred.fence()
                    with tracer.span("trainer.readback") as readback:
                        prev = (
                            None if governed
                            else deferred.push(done, metrics)
                        )
                        if prev:
                            _raise_counters(tracer, prev[1])
                    now = time.perf_counter()
                    step_metrics = {
                        "loss": last_loss,  # device array: sync if read
                        "loss_lag1": prev[1]["loss"] if prev else None,
                        "step_time_s": now - t_mark,
                    }
                    t_mark = now
                else:
                    with tracer.span("trainer.fence") as fence:
                        jax.block_until_ready(last_loss)
                    with tracer.span("trainer.readback") as readback:
                        loss_host = float(last_loss)
                        _raise_counters(tracer, metrics)
                    step_metrics = {
                        "loss": loss_host,
                        "step_time_s": time.perf_counter() - dispatch.start,
                    }
                if self._phases is not None:
                    phases = self._phases.split(
                        input_span.duration_s, dispatch.duration_s,
                        fence.duration_s, readback.duration_s,
                    )
                    if self._report and done % self._phase_every == 0:
                        emit(
                            EventKind.STEP_PHASES, step=done,
                            step_s=step_metrics["step_time_s"],
                            **({"governed": True} if governed else {}),
                            **phases,
                        )
                tokens = batch_token_count(batch)
                if tokens:
                    step_metrics["tokens_per_s"] = (
                        tokens / step_metrics["step_time_s"]
                    )
                if self._lr_schedule is not None:
                    step_metrics["lr"] = float(self._lr_schedule(done))
                with tracer.span("trainer.callbacks"):
                    self._fire("on_step_end", done, step_metrics)
                if (eval_batches is not None and eval_every
                        and done % eval_every == 0):
                    last_eval = self.evaluate(
                        eval_batches(), max_batches=eval_max_batches
                    )
                    evaluated_at = done
                    self._fire("on_evaluate", done, last_eval)
                if self.should_stop:
                    logger.info("callback requested stop at step %s", done)
                    break
        deferred.flush()  # drain the lag-1 slot before the boundary work
        self._progress.flush(done if done > start else None)
        if eval_batches is not None and evaluated_at != done:
            last_eval = self.evaluate(
                eval_batches(), max_batches=eval_max_batches
            )
            self._fire("on_evaluate", done, last_eval)
        self._fire("on_train_end", done)
        loss = float(last_loss)  # final sync: bit-identical to the sync loop
        logger.info("trainer finished at step %s (loss %.5f)", done, loss)
        out = {"step": done, "loss": loss}
        out.update(last_eval)
        return out

    def close(self):
        if self._ckpt is not None:
            self._ckpt.close()
