"""Pipeline-parallelism tests on the 8-device CPU mesh.

The GPipe schedule must be *exact*: its logits equal running the same
stage parameters sequentially (validated against a dense GPT fed the
reshaped stage params), and training under ParallelSpec(pipe=K) must
match the same pipelined model on one device.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.accel import ParallelSpec, auto_accelerate
from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn


def pipe_cfg(stages=2, microbatches=0, **kw):
    return dataclasses.replace(
        GPTConfig.tiny(), dtype=jnp.float32, num_layers=4,
        pipeline_stages=stages, pipeline_microbatches=microbatches, **kw
    )


def token_loss(module, params, batch):
    return loss_fn(module.apply({"params": params}, batch), batch)


def run_training(spec, steps=3, cfg=None):
    cfg = cfg or pipe_cfg()
    model = GPT(cfg)
    opt = optax.adamw(1e-3)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
    )
    res = auto_accelerate(model, opt, tokens, token_loss, spec=spec)
    state = res.state
    batch = jax.device_put(tokens, res.batch_sharding)
    losses = []
    for _ in range(steps):
        state, m = res.train_step(state, batch)
        losses.append(float(m["loss"]))
    res.state = state
    return losses, res


class TestScheduleExactness:
    def test_matches_sequential_stages(self):
        """Pipelined logits == a dense GPT running the same weights: the
        [P, L/P, ...] stage-stacked block params reshape to the dense
        model's [L, ...] scan stack; embeddings/ln_f are copied over."""
        cfg = pipe_cfg(stages=2, microbatches=2)
        model = GPT(cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (4, 16), 0, cfg.vocab_size
        )
        import flax.linen as nn

        params = nn.meta.unbox(
            model.init(jax.random.PRNGKey(42), tokens)["params"]
        )
        logits_pipe = model.apply({"params": params}, tokens)

        dense_cfg = dataclasses.replace(
            cfg, pipeline_stages=0, pipeline_microbatches=0
        )
        stage_blocks = params["pipeline"]["ticks"]["stages"]["stage"]["blocks"]
        dense_params = {
            k: v for k, v in params.items() if k != "pipeline"
        }
        dense_params["blocks"] = jax.tree_util.tree_map(
            lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]),
            stage_blocks,
        )
        logits_dense = GPT(dense_cfg).apply(
            {"params": dense_params}, tokens
        )
        np.testing.assert_allclose(
            np.asarray(logits_pipe), np.asarray(logits_dense),
            rtol=1e-5, atol=1e-5,
        )

    def test_more_microbatches_same_result(self):
        cfg2 = pipe_cfg(stages=2, microbatches=2)
        cfg4 = pipe_cfg(stages=2, microbatches=4)
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (4, 16), 0, cfg2.vocab_size
        )
        import flax.linen as nn

        params = nn.meta.unbox(
            GPT(cfg2).init(jax.random.PRNGKey(3), tokens)["params"]
        )
        out2 = GPT(cfg2).apply({"params": params}, tokens)
        out4 = GPT(cfg4).apply({"params": params}, tokens)
        np.testing.assert_allclose(
            np.asarray(out2), np.asarray(out4), rtol=1e-5, atol=1e-5
        )


class TestPipelinedTraining:
    @pytest.fixture(scope="class")
    def baseline(self):
        return run_training(ParallelSpec())[0]

    @pytest.mark.parametrize(
        "spec",
        [
            ParallelSpec(pipe=2),
            ParallelSpec(data=2, pipe=2),
            ParallelSpec(data=2, pipe=2, tensor=2),
        ],
        ids=["pp", "dp-pp", "dp-pp-tp"],
    )
    def test_matches_single_device(self, spec, baseline):
        losses, _ = run_training(spec)
        np.testing.assert_allclose(losses, baseline, rtol=2e-5, atol=2e-5)

    def test_stage_params_sharded(self):
        _, res = run_training(ParallelSpec(pipe=2), steps=1)
        qkv = (
            res.state["params"]["pipeline"]["ticks"]["stages"]["stage"]
            ["blocks"]["qkv"]["kernel"]
        )
        # [P, L/P, D, 3D]: stage dim sharded 2-way over pipe
        shard = qkv.addressable_shards[0]
        assert shard.data.shape[0] == qkv.shape[0] // 2

    def test_loss_decreases(self):
        losses, _ = run_training(
            ParallelSpec(data=2, pipe=2), steps=5,
            cfg=pipe_cfg(stages=2, microbatches=4),
        )
        assert losses[-1] < losses[0]


class TestSpecValidation:
    def test_pipe_without_stage_axis_rejected(self):
        cfg = dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32)
        model = GPT(cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
        )
        with pytest.raises(ValueError, match="stage"):
            auto_accelerate(
                model, optax.adamw(1e-3), tokens, token_loss,
                spec=ParallelSpec(pipe=2),
            )

    def test_expert_without_expert_axis_rejected(self):
        cfg = dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32)
        model = GPT(cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
        )
        with pytest.raises(ValueError, match="expert"):
            auto_accelerate(
                model, optax.adamw(1e-3), tokens, token_loss,
                spec=ParallelSpec(expert=2),
            )

    def test_bad_layer_split_rejected(self):
        with pytest.raises(ValueError, match="divisible"):
            pipe_cfg(stages=3)

    def test_circular_needs_enough_microbatches(self):
        from dlrover_tpu.models.gpt import GPT as _GPT

        cfg = dataclasses.replace(
            pipe_cfg(stages=4, microbatches=2), num_layers=8,
            pipeline_repeats=2,
        )
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
        )
        with pytest.raises(ValueError, match="microbatches >= stages"):
            _GPT(cfg).init(jax.random.PRNGKey(0), tokens)


def _stack_chunks_dense(bank, stages, repeats):
    """Reorder a circular [P, C, Lc, ...] weight bank into the dense
    model's [L, ...] layer stack (chunk j = c*P + p covers layers
    [j*Lc, (j+1)*Lc))."""
    def to_dense(a):
        parts = []
        for j in range(stages * repeats):
            parts.append(a[j % stages, j // stages])
        return jnp.concatenate(parts, axis=0)

    return jax.tree_util.tree_map(to_dense, bank)


class TestCircularSchedule:
    """The interleaved/circular schedule: exact numerics
    and a measured bubble improvement over GPipe."""

    def test_matches_sequential_stages(self):
        cfg = pipe_cfg(stages=2, microbatches=4)
        cfg = dataclasses.replace(cfg, pipeline_repeats=2)
        model = GPT(cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (8, 16), 0, cfg.vocab_size
        )
        import flax.linen as nn

        params = nn.meta.unbox(
            model.init(jax.random.PRNGKey(42), tokens)["params"]
        )
        logits_circ = model.apply({"params": params}, tokens)

        dense_cfg = dataclasses.replace(
            cfg, pipeline_stages=0, pipeline_repeats=1,
            pipeline_microbatches=0,
        )
        dense_params = {
            k: v for k, v in params.items() if k != "pipeline"
        }
        dense_params["blocks"] = _stack_chunks_dense(
            params["pipeline"]["bank"]["blocks"], 2, 2
        )
        logits_dense = GPT(dense_cfg).apply(
            {"params": dense_params}, tokens
        )
        np.testing.assert_allclose(
            np.asarray(logits_circ), np.asarray(logits_dense),
            rtol=1e-5, atol=1e-5,
        )

    def test_bubble_cut_vs_gpipe(self):
        """The schedule-cost model: circular with C repeats cuts the
        drain bubble ~C x (wall-clock in full-forward units)."""
        from dlrover_tpu.accel.pipeline import schedule_cost

        m, p = 8, 4
        gpipe = schedule_cost(m, p)                      # (8+3)/4 = 2.75
        circ2 = schedule_cost(m, p, num_repeats=2)       # (16+3)/8
        circ4 = schedule_cost(m, p, num_repeats=4)       # (32+3)/16
        ideal = m / p
        assert gpipe > circ2 > circ4 > ideal
        # bubble overheads: (cost - ideal)/ideal
        assert (circ2 - ideal) / (gpipe - ideal) == pytest.approx(
            0.5, abs=0.01
        )
        assert (circ4 - ideal) / (gpipe - ideal) == pytest.approx(
            0.25, abs=0.01
        )

    def test_trains_sharded_matches_single_device(self):
        cfg = dataclasses.replace(
            pipe_cfg(stages=2, microbatches=4), pipeline_repeats=2
        )
        base, _ = run_training(ParallelSpec(), cfg=cfg)
        sharded, _ = run_training(ParallelSpec(data=2, pipe=2), cfg=cfg)
        np.testing.assert_allclose(sharded, base, rtol=2e-5, atol=2e-5)

    def test_bank_sharded_over_pipe(self):
        cfg = dataclasses.replace(
            pipe_cfg(stages=2, microbatches=4), pipeline_repeats=2
        )
        _, res = run_training(ParallelSpec(pipe=2), steps=1, cfg=cfg)
        qkv = (
            res.state["params"]["pipeline"]["bank"]["blocks"]["qkv"]
            ["kernel"]
        )
        # [P, C, Lc, D, 3D]: stage dim sharded over pipe, C local.
        shard = qkv.addressable_shards[0]
        assert shard.data.shape[0] == qkv.shape[0] // 2
        assert shard.data.shape[1] == qkv.shape[1]


class TestVocabOverPipe:
    """The embedding and LM head — the two largest
    tensors — must not be replicated per pipe device. The SPMD analog of
    the reference's first/last-stage placement shards their vocab dim
    over the pipe axis, balancing vocab memory across all stages."""

    def test_embed_and_head_sharded_over_pipe(self):
        cfg = pipe_cfg(stages=2, microbatches=2)
        _, res = run_training(ParallelSpec(pipe=2), steps=1, cfg=cfg)
        emb = res.state["params"]["wte"]["embedding"]
        assert emb.addressable_shards[0].data.shape[0] == emb.shape[0] // 2
        # per-device vocab bytes = V/P: balanced, not dumped on one stage
        per_dev = emb.addressable_shards[0].data.nbytes
        assert per_dev * 2 == sum(
            s.data.nbytes for s in emb.addressable_shards[:2]
        )

    def test_training_exact_with_vocab_sharding(self):
        """Sharding vocab over pipe is placement only: training matches
        the single-device baseline exactly."""
        cfg = pipe_cfg(stages=2, microbatches=2)
        base, _ = run_training(ParallelSpec(), cfg=cfg)
        pp, _ = run_training(ParallelSpec(data=2, pipe=2), cfg=cfg)
        np.testing.assert_allclose(pp, base, rtol=2e-5, atol=2e-5)

    def test_search_memory_model_sees_vocab_split(self):
        """state_bytes_per_device must price the vocab split: on a
        vocab-dominated model, pipe=2 roughly halves per-device state."""
        from dlrover_tpu.accel import auto_accelerate  # noqa: F401
        from dlrover_tpu.accel.search import state_bytes_per_device
        import flax.linen as nn

        cfg = pipe_cfg(stages=2, microbatches=2)
        model = GPT(cfg)
        tokens = jnp.zeros((4, 16), jnp.int32)
        abstract = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), tokens)
        )["params"]
        one = state_bytes_per_device(abstract, ParallelSpec())
        split = state_bytes_per_device(abstract, ParallelSpec(pipe=2))
        # tiny cfg is vocab-dominated: expect a large drop, > 35%
        assert split < one * 0.65, (one, split)


class TestCircularTraffic:
    """The chunk selection must not touch the whole
    weight bank every tick. The default "slice" lowering reads 1/C via a
    per-stage dynamic index; "onehot" is kept only as the baseline it
    is compared with (docs/pipeline_schedules.md)."""

    @staticmethod
    def _chunk(n, d):
        import flax.linen as nn

        class NLayers(nn.Module):
            @nn.compact
            def __call__(self, x):
                for i in range(n):
                    x = x + nn.Dense(d, use_bias=False, name=f"l{i}")(x)
                return x

        return NLayers

    def test_slice_and_onehot_selection_identical(self):
        """The selection lowering is semantics-free: both modes produce
        bit-identical outputs from the same bank."""
        from dlrover_tpu.accel.pipeline import CircularPipeline

        d = 32
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 4, d))
        mk = self._chunk(2, d)
        pipes = [
            CircularPipeline(make_stage=mk, num_stages=2, num_repeats=2,
                             num_microbatches=4, chunk_select=mode)
            for mode in ("slice", "onehot")
        ]
        params = pipes[0].init(jax.random.PRNGKey(1), x)
        y_slice = pipes[0].apply(params, x)
        y_onehot = pipes[1].apply(params, x)
        np.testing.assert_array_equal(
            np.asarray(y_slice), np.asarray(y_onehot)
        )

    def test_per_tick_flops_are_one_over_c(self):
        """XLA cost analysis counts the scan body once, so the analyzed
        FLOPs compare per-tick work: a C=2 circular tick must do ~1/2
        the FLOPs of a GPipe tick over the same total layers."""
        from dlrover_tpu.accel.pipeline import CircularPipeline, Pipeline

        d = 128
        x = jnp.zeros((4, 8, d))

        def flops(mod):
            params = mod.init(jax.random.PRNGKey(0), x)
            c = (
                jax.jit(lambda p, xx: mod.apply(p, xx))
                .lower(params, x).compile().cost_analysis()
            )
            if isinstance(c, list):
                c = c[0]
            return c["flops"]

        gp = flops(Pipeline(make_stage=lambda: self._chunk(4, d)(),
                            num_stages=2, num_microbatches=4))
        cc = flops(CircularPipeline(
            make_stage=lambda: self._chunk(2, d)(),
            num_stages=2, num_repeats=2, num_microbatches=4,
        ))
        assert cc / gp == pytest.approx(0.5, rel=0.1), (cc, gp)


class TestMoEPipeline:
    """MoE composes with both schedules: the aux loss rides the carry
    (replaces round-3's rejection test)."""

    def _exact(self, repeats):
        cfg = pipe_cfg(stages=2, microbatches=4, num_experts=2)
        cfg = dataclasses.replace(cfg, pipeline_repeats=repeats)
        model = GPT(cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (8, 16), 0, cfg.vocab_size
        )
        import flax.linen as nn

        params = nn.meta.unbox(
            model.init(jax.random.PRNGKey(7), tokens)["params"]
        )
        logits, aux = model.apply({"params": params}, tokens)

        dense_cfg = dataclasses.replace(
            cfg, pipeline_stages=0, pipeline_repeats=1,
            pipeline_microbatches=0,
        )
        dense_params = {
            k: v for k, v in params.items() if k != "pipeline"
        }
        if repeats > 1:
            dense_params["blocks"] = _stack_chunks_dense(
                params["pipeline"]["bank"]["blocks"], 2, repeats
            )
        else:
            sb = params["pipeline"]["ticks"]["stages"]["stage"]["blocks"]
            dense_params["blocks"] = jax.tree_util.tree_map(
                lambda a: a.reshape(
                    a.shape[0] * a.shape[1], *a.shape[2:]
                ),
                sb,
            )
        # The MoE aux loss is a per-dispatch-group statistic (expert
        # fractions + capacity apply per routed group), so the pipelined
        # model's ground truth is the dense model run per-microbatch —
        # the same semantics grad accumulation has.
        m = cfg.pipeline_microbatches
        mb = tokens.shape[0] // m
        logits_parts, aux_parts = [], []
        for i in range(m):
            lo, ao = GPT(dense_cfg).apply(
                {"params": dense_params}, tokens[i * mb:(i + 1) * mb]
            )
            logits_parts.append(lo)
            aux_parts.append(ao)
        logits_d = jnp.concatenate(logits_parts, axis=0)
        aux_d = jnp.mean(jnp.stack(aux_parts))
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(logits_d),
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            float(aux), float(aux_d), rtol=1e-5
        )

    def test_gpipe_moe_exact(self):
        self._exact(repeats=1)

    def test_circular_moe_exact(self):
        self._exact(repeats=2)

    def test_moe_pp_ep_trains(self):
        """dp x pp x ep: the composition round 3 rejected."""
        from dlrover_tpu.models.gpt import moe_loss_fn

        cfg = pipe_cfg(stages=2, microbatches=2, num_experts=2)
        model = GPT(cfg)
        opt = optax.adamw(1e-3)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
        )

        def moe_token_loss(module, params, batch):
            return moe_loss_fn(
                module.apply({"params": params}, batch), batch
            )

        res = auto_accelerate(
            model, opt, tokens, moe_token_loss,
            spec=ParallelSpec(data=2, pipe=2, expert=2),
        )
        state = res.state
        batch = jax.device_put(tokens, res.batch_sharding)
        losses = []
        for _ in range(3):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]


class TestLlamaPipeline:
    def test_llama_pp_trains(self):
        """LLaMA pipeline_stages (round-3 gap: the flagship family had
        no pipeline wiring)."""
        from dlrover_tpu.models.llama import Llama, LlamaConfig

        cfg = dataclasses.replace(
            LlamaConfig.tiny(), dtype=jnp.float32, num_layers=4,
            pipeline_stages=2, pipeline_microbatches=4,
        )
        model = Llama(cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
        )
        res = auto_accelerate(
            model, optax.adamw(1e-3), tokens, token_loss,
            spec=ParallelSpec(data=2, pipe=2),
        )
        state = res.state
        batch = jax.device_put(tokens, res.batch_sharding)
        losses = []
        for _ in range(3):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]
