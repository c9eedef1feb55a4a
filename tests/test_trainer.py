"""High-level Trainer tests (SURVEY §2.5 AtorchTrainer analog)."""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import optax

from dlrover_tpu.accel import ParallelSpec
from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn
from dlrover_tpu.train.trainer import Trainer


def tiny_cfg():
    return dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32)


def token_loss(module, params, batch):
    return loss_fn(module.apply({"params": params}, batch), batch)


def batches(cfg, n=10_000, batch=8):
    key = jax.random.PRNGKey(7)
    for i in range(n):
        yield jax.random.randint(
            jax.random.fold_in(key, i), (batch, 16), 0, cfg.vocab_size
        )


class TestTrainer:
    def test_fit_trains(self, job_name):
        cfg = tiny_cfg()
        trainer = Trainer(
            GPT(cfg), optax.adamw(1e-3), token_loss,
            next(batches(cfg)), spec=ParallelSpec(data=2),
        )
        first = trainer.fit(batches(cfg), steps=2)
        second = trainer.fit(batches(cfg), steps=6, start_step=2)
        assert second["step"] == 6
        assert second["loss"] < first["loss"]

    def test_fit_resumes_from_checkpoint(self, tmp_path, job_name):
        cfg = tiny_cfg()
        ckpt = str(tmp_path / "ckpts")

        def make():
            return Trainer(
                GPT(cfg), optax.adamw(1e-3), token_loss,
                next(batches(cfg)), spec=ParallelSpec(),
                checkpoint_dir=ckpt, persist_every=5,
            )

        t1 = make()
        out = t1.fit(batches(cfg), steps=5)
        assert out["step"] == 5
        t1.close()

        t2 = make()  # "restarted process"
        resumed = t2.restore()
        assert resumed == 5, "did not resume from the persisted step"
        out = t2.fit(batches(cfg), steps=8, start_step=resumed)
        assert out["step"] == 8
        assert int(jax.device_get(t2.state["step"])) == 8
        t2.close()

    def test_data_exhaustion_stops_cleanly(self, job_name):
        cfg = tiny_cfg()
        trainer = Trainer(
            GPT(cfg), optax.adamw(1e-3), token_loss,
            next(batches(cfg)), spec=ParallelSpec(),
        )
        out = trainer.fit(
            itertools.islice(batches(cfg), 3), steps=100
        )
        assert out["step"] == 3

    def test_grad_accum_passthrough(self, job_name):
        cfg = tiny_cfg()
        trainer = Trainer(
            GPT(cfg), optax.adamw(1e-3), token_loss,
            next(batches(cfg)), spec=ParallelSpec(), grad_accum=2,
        )
        out = trainer.fit(batches(cfg), steps=2)
        assert out["step"] == 2


class TestTrainerSurface:
    """Evaluation, callbacks, LR-schedule wiring
    (parity: atorch_trainer.py's train loop carries all three)."""

    def test_evaluate_runs_forward_only(self, job_name):
        cfg = tiny_cfg()
        fixed = list(itertools.islice(batches(cfg), 3))  # learnable set
        trainer = Trainer(
            GPT(cfg), optax.adamw(1e-2), token_loss,
            fixed[0], spec=ParallelSpec(),
        )
        before = trainer.evaluate(fixed)
        assert before["eval_batches"] == 3
        trainer.fit(itertools.cycle(fixed), steps=30)
        after = trainer.evaluate(fixed)
        assert after["eval_loss"] < before["eval_loss"]
        # eval is forward-only: params untouched by evaluate itself
        again = trainer.evaluate(fixed)
        assert again["eval_loss"] == pytest.approx(
            after["eval_loss"], rel=1e-6
        )

    def test_fit_interleaves_eval_and_callbacks(self, job_name):
        from dlrover_tpu.train.trainer import (
            LoggingCallback,
            TrainerCallback,
        )

        events = []
        step_metrics_log = []

        # NOTE: assertions must happen AFTER fit() — the trainer
        # swallows callback exceptions by design, so in-callback
        # asserts can never fail the test.
        class Recorder(TrainerCallback):
            def on_train_begin(self, trainer, start):
                events.append(("begin", start))

            def on_step_end(self, trainer, step, metrics):
                events.append(("step", step))
                step_metrics_log.append((step, dict(metrics)))

            def on_evaluate(self, trainer, step, metrics):
                events.append(("eval", step, metrics["eval_loss"]))

            def on_train_end(self, trainer, step):
                events.append(("end", step))

        schedule = optax.cosine_decay_schedule(1e-2, 100)
        cfg = tiny_cfg()
        trainer = Trainer(
            GPT(cfg), optax.chain(
                optax.scale_by_adam(),
                optax.scale_by_schedule(lambda s: -schedule(s)),
            ),
            token_loss, next(batches(cfg)), spec=ParallelSpec(),
            callbacks=[Recorder(), LoggingCallback(every=2)],
            lr_schedule=schedule,
        )
        out = trainer.fit(
            batches(cfg), steps=4,
            eval_batches=lambda: itertools.islice(batches(cfg), 2),
            eval_every=2,
        )
        assert "eval_loss" in out
        kinds = [e[0] for e in events]
        assert kinds[0] == "begin" and kinds[-1] == "end"
        assert kinds.count("step") == 4
        # step 2 and step 4 in-loop; the final eval dedups against the
        # step-4 one instead of re-running it
        assert kinds.count("eval") == 2
        for step, metrics in step_metrics_log:
            assert "loss" in metrics and "tokens_per_s" in metrics
            assert metrics["lr"] == pytest.approx(
                float(schedule(step)), rel=1e-6
            )

    def test_callback_early_stop(self, job_name):
        from dlrover_tpu.train.trainer import TrainerCallback

        class StopAt3(TrainerCallback):
            def on_step_end(self, trainer, step, metrics):
                if step >= 3:
                    trainer.should_stop = True

        cfg = tiny_cfg()
        trainer = Trainer(
            GPT(cfg), optax.adamw(1e-3), token_loss,
            next(batches(cfg)), spec=ParallelSpec(),
            callbacks=[StopAt3()],
        )
        out = trainer.fit(batches(cfg), steps=100)
        assert out["step"] == 3


class TestAsyncPipeline:
    """The async step pipeline (docs/async_pipeline.md): double-buffered
    device prefetch + lag-1 metric readback must change WHEN values are
    read back, never WHAT is computed."""

    @staticmethod
    def _recorder():
        from dlrover_tpu.train.trainer import TrainerCallback

        losses, lag1 = [], []

        class Rec(TrainerCallback):
            def on_step_end(self, trainer, step, metrics):
                losses.append(float(metrics["loss"]))
                lag1.append(metrics.get("loss_lag1"))

        return Rec(), losses, lag1

    def _make(self, cfg, cb, **kw):
        return Trainer(
            GPT(cfg), optax.adamw(1e-3), token_loss,
            next(batches(cfg)), spec=ParallelSpec(),
            callbacks=[cb] if cb else (), **kw,
        )

    def test_pipelined_matches_sync_bit_identical(self, job_name):
        cfg = tiny_cfg()
        rec_s, sync_losses, _ = self._recorder()
        out_sync = self._make(cfg, rec_s).fit(
            batches(cfg), steps=6, pipeline=False
        )
        rec_p, pipe_losses, pipe_lag1 = self._recorder()
        out_pipe = self._make(cfg, rec_p).fit(
            batches(cfg), steps=6, pipeline=True
        )
        # same init seed + same batch stream: the pipelined loop must
        # reproduce the sync trajectory exactly, not approximately
        assert pipe_losses == sync_losses
        assert out_pipe["loss"] == out_sync["loss"]
        assert out_pipe["step"] == out_sync["step"] == 6
        # lag-1 contract: step N's callback gets step N-1's float free
        assert pipe_lag1[0] is None
        assert pipe_lag1[1:] == pipe_losses[:-1]

    def test_pipelined_step_metrics_shape(self, job_name):
        cfg = tiny_cfg()
        rows = []
        from dlrover_tpu.train.trainer import TrainerCallback

        class Rec(TrainerCallback):
            def on_step_end(self, trainer, step, metrics):
                rows.append(dict(metrics))

        self._make(cfg, Rec()).fit(batches(cfg), steps=3)
        for row in rows:
            assert isinstance(row["loss"], jax.Array)  # lazy: no sync
            assert row["step_time_s"] > 0
            # tokens_per_s uses real leaf sizes, not np.shape(dict)==()
            assert row["tokens_per_s"] == pytest.approx(
                8 * 16 / row["step_time_s"]
            )

    def test_pipelined_data_exhaustion(self, job_name):
        cfg = tiny_cfg()
        out = self._make(cfg, None).fit(
            itertools.islice(batches(cfg), 4), steps=100, pipeline=True
        )
        assert out["step"] == 4

    def test_prefetched_iterator_passthrough(self, job_name):
        from dlrover_tpu.train.data.device_prefetch import (
            DevicePrefetchIterator,
        )

        cfg = tiny_cfg()
        trainer = self._make(cfg, None)
        it = DevicePrefetchIterator(
            itertools.islice(batches(cfg), 5),
            trainer.batch_sharding, depth=3,
        )
        out = trainer.fit(it, steps=100)  # not re-wrapped
        assert out["step"] == 5

    def test_memory_snapshot_safe_under_runahead(self, tmp_path, job_name):
        """Flash MEMORY snapshots must never observe donated buffers
        even though the pipelined host runs ahead of the device: the
        engine's own D2H copies are dispatched before the next donated
        step, so the restored state equals a deterministic sync rerun
        stopped at the landed step."""
        cfg = tiny_cfg()
        trainer = self._make(
            cfg, None,
            checkpoint_dir=str(tmp_path / "flash"),
            persist_every=1000,  # MEMORY-only path
        )
        trainer.fit(batches(cfg), steps=5, pipeline=True)
        assert trainer._ckpt.engine.wait_staged(30.0)
        step, restored = trainer._ckpt.load_checkpoint(trainer.state)
        # async staging may skip a step while the saver holds the shard;
        # whatever landed must be a consistent, uncorrupted state
        assert 1 <= step <= 5
        ref = self._make(cfg, None)
        ref.fit(batches(cfg), steps=step, pipeline=False)
        for got, want in zip(
            jax.tree_util.tree_leaves(restored["params"]),
            jax.tree_util.tree_leaves(ref.state["params"]),
        ):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want)
            )
        trainer.close()


class TestPhaseTelemetry:
    """Per-step phase breakdown (straggler telemetry): pure bookkeeping
    around fences the loop already takes — bit-identical loss, no sync
    added to the run-ahead step, step.phases events on the wire."""

    def _make(self, cfg, cb=None):
        return Trainer(
            GPT(cfg), optax.adamw(1e-3), token_loss,
            next(batches(cfg)), spec=ParallelSpec(),
            callbacks=[cb] if cb else (),
        )

    def test_phases_on_is_bit_identical_to_off(self, job_name,
                                               monkeypatch):
        from dlrover_tpu.train.trainer import TrainerCallback

        def run(phases_on):
            monkeypatch.setenv("DLROVER_TPU_STRAGGLER_PHASES",
                               "1" if phases_on else "0")
            losses = []

            class Rec(TrainerCallback):
                def on_step_end(self, trainer, step, metrics):
                    losses.append(float(metrics["loss"]))

            cfg = tiny_cfg()
            t = self._make(cfg, Rec())
            assert (t.phase_breakdown is not None) == phases_on
            out = t.fit(batches(cfg), steps=6, pipeline=True)
            return losses, out["loss"]

        off_losses, off_final = run(False)
        on_losses, on_final = run(True)
        assert on_losses == off_losses
        assert on_final == off_final

    def test_phase_timing_keeps_runahead_loss_lazy(self, job_name,
                                                   monkeypatch):
        """The fence() split blocks lag-1 only: with phases on, the
        current step's loss must still be an unsynced jax.Array and the
        lag-1 float contract must hold."""
        from dlrover_tpu.train.trainer import TrainerCallback

        monkeypatch.setenv("DLROVER_TPU_STRAGGLER_PHASES", "1")
        rows = []

        class Rec(TrainerCallback):
            def on_step_end(self, trainer, step, metrics):
                rows.append(metrics)

        cfg = tiny_cfg()
        t = self._make(cfg, Rec())
        t.fit(batches(cfg), steps=4, pipeline=True)
        assert all(isinstance(r["loss"], jax.Array) for r in rows)
        assert rows[0]["loss_lag1"] is None
        assert [r["loss_lag1"] for r in rows[1:]] == [
            pytest.approx(float(r["loss"])) for r in rows[:-1]
        ]
        rep = t.phase_breakdown.report()
        for key in ("input_s", "compute_s", "collective_s",
                    "readback_s"):
            assert rep[key]["p99_s"] >= 0.0
        assert t.phase_breakdown.stats["compute_s"].count == 4

    def test_step_phase_events_reach_the_sink(self, job_name):
        from dlrover_tpu.observability import events as events_mod
        from dlrover_tpu.observability.event_log import EventLog
        from dlrover_tpu.observability.events import EventKind

        log = EventLog()
        events_mod.install_sink(log.append)
        events_mod.set_identity(3, "worker")
        try:
            cfg = tiny_cfg()
            self._make(cfg).fit(batches(cfg), steps=3, pipeline=True)
        finally:
            events_mod.reset()
        evs = log.events(kinds=[EventKind.STEP_PHASES])
        assert [e.args["step"] for e in evs] == [1, 2, 3]
        assert all(e.node_id == 3 for e in evs)
        for e in evs:
            for key in ("input_s", "compute_s", "collective_s",
                        "readback_s", "step_s"):
                assert e.args[key] >= 0.0
