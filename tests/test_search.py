"""Strategy-search engine tests.

The search must pick each parallelism family on its own, given only a
model + device count: fsdp for a too-big dense model, ``expert`` for an
MoE model, ``seq`` for a long-context batch-1 model, ``pipe`` when even
fully-sharded state exceeds HBM (the pipeline composition halves the
FSDP all-gather traffic at equal memory). Parity target: the reference's
acceleration engine + strategy-generation algorithms
(``atorch/atorch/auto/engine/acceleration_engine.py:13``,
``sg_algo/bayes_opt_sg.py``) — here the space is small enough to
enumerate exactly.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.accel import ParallelSpec, auto_accelerate
from dlrover_tpu.accel.search import (
    ModelProfile,
    enumerate_specs,
    estimate,
    reconfigure_module,
    search_spec,
    state_bytes_per_device,
)
from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn

HBM_16G = 16e9


def profile_of(cfg, **over):
    p = ModelProfile.from_config(cfg)
    return dataclasses.replace(p, **over) if over else p


class TestEnumeration:
    def test_covers_all_families_when_model_supports_them(self):
        cfg = GPTConfig(
            vocab_size=50264, max_seq_len=2048, num_layers=8,
            num_heads=8, d_model=512,
        )
        specs = enumerate_specs(profile_of(cfg), 8, batch_size=8)
        axes_seen = set()
        for s in specs:
            for name in ("data", "fsdp", "tensor", "seq", "pipe"):
                if getattr(s, name) > 1:
                    axes_seen.add(name)
        assert axes_seen == {"data", "fsdp", "tensor", "seq", "pipe"}
        assert all(s.total == 8 for s in specs)

    def test_gating(self):
        # No ring/pipeline support, no experts, odd head count: the
        # space degrades to data/fsdp only.
        p = ModelProfile.from_params(1_000_000)
        specs = enumerate_specs(p, 8, batch_size=8)
        assert specs
        for s in specs:
            assert s.tensor == s.seq == s.expert == s.pipe == 1

    def test_batch_divisibility(self):
        cfg = GPTConfig.tiny()
        specs = enumerate_specs(profile_of(cfg), 8, batch_size=2)
        assert all(s.data * s.fsdp in (1, 2) for s in specs)


class TestChoices:
    """Each family must be chosen on its own merits."""

    def test_small_dense_pure_dp(self):
        cfg = GPTConfig.tiny()
        (spec, est), *_ = search_spec(
            profile_of(cfg), 8, batch_size=8, hbm=HBM_16G
        )
        assert spec == ParallelSpec(data=8)
        assert est.fits(HBM_16G)

    def test_too_big_dense_gets_fsdp(self):
        # GPT-2-xl class: 1.5B params * 16 B/param = 25 GB state.
        cfg = GPTConfig.gpt2_xl()
        (spec, est), *_ = search_spec(
            profile_of(cfg), 8, batch_size=8, hbm=HBM_16G
        )
        assert spec.fsdp > 1
        assert est.fits(HBM_16G)

    def test_moe_model_gets_expert_parallel(self):
        # Experts hold ~8x the dense params: replicating them under pure
        # DP wastes memory and FSDP all-gathers the full expert set every
        # layer; EP's all-to-all is the cheap option.
        cfg = GPTConfig(
            vocab_size=50264, max_seq_len=1024, num_layers=16,
            num_heads=16, d_model=2048, num_experts=8, remat=True,
        )
        (spec, est), *_ = search_spec(
            profile_of(cfg), 8, batch_size=8, hbm=HBM_16G
        )
        assert spec.expert > 1
        assert est.fits(HBM_16G)

    def test_long_context_gets_seq(self):
        # Batch 1 at 32k context: the batch axis cannot shard, so only
        # seq parallelism divides the activation footprint.
        cfg = GPTConfig(
            vocab_size=50264, max_seq_len=32768, num_layers=24,
            num_heads=16, d_model=2048, remat=True,
        )
        (spec, _), *_ = search_spec(
            profile_of(cfg), 8, batch_size=1, hbm=HBM_16G
        )
        assert spec.seq > 1

    def test_pipe_when_fsdp_not_enough(self):
        # State >> 8 x HBM: nothing fits even fully sharded, so the
        # ranking is comm-driven among maximally-sharded candidates.
        # Over a slow interconnect (hosts linked by DCN, not ICI) the
        # per-layer FSDP all-gathers and TP all-reduces are ruinous;
        # composing pipe halves the gathered volume at equal memory and
        # its own traffic is one activation per microbatch per boundary.
        # This is exactly how real TPU pods place PP: across the slow
        # links, FSDP/TP inside the fast ones.
        cfg = GPTConfig(
            vocab_size=50264, max_seq_len=4096, num_layers=48,
            num_heads=32, d_model=8192, remat=True,
        )
        ranked = search_spec(
            profile_of(cfg), 8, batch_size=32, hbm=HBM_16G,
            ici_bw=2e9,  # DCN-class
        )
        spec = ranked[0][0]
        assert spec.pipe > 1

    def test_fast_ici_prefers_fsdp_over_pipe(self):
        # Same model on real ICI: the all-gathers overlap with compute
        # and the pipeline bubble is pure loss — fsdp/tp must win.
        cfg = GPTConfig(
            vocab_size=50264, max_seq_len=4096, num_layers=48,
            num_heads=32, d_model=8192, remat=True,
        )
        ranked = search_spec(
            profile_of(cfg), 8, batch_size=32, hbm=HBM_16G
        )
        assert ranked[0][0].pipe == 1

    def test_pipe_priced_by_weight_traffic_floor(self):
        """Pipeline ticks re-read resident stage weights,
        so at tiny batch (memory-bound) a pipelined step is floored by
        HBM traffic, not the bubble-adjusted compute. The estimate must
        carry that floor and it must grow with the tick count."""
        from dlrover_tpu.accel.search import estimate

        cfg = GPTConfig(
            vocab_size=50264, max_seq_len=2048, num_layers=32,
            num_heads=32, d_model=4096, remat=True,
        )
        p = profile_of(cfg)
        no_pipe = estimate(
            p, ParallelSpec(fsdp=8), batch_size=8, hbm=HBM_16G
        )
        pipe = estimate(
            p, ParallelSpec(fsdp=2, pipe=4), batch_size=8, hbm=HBM_16G
        )
        assert no_pipe.hbm_s == 0.0
        assert pipe.hbm_s > 0.0
        # ticks x resident bytes / HBM_BW; resident = stage-bank layer
        # params. GPT ties its LM head, so the out-of-pipe vocab params
        # are V*d + seq*d (cfg.vocab_param_count), not 2*V*d.
        m = 4  # _pipe_microbatches(4, 8, 2): per-shard batch 4 -> M=4
        layer_params = p.param_count - cfg.vocab_param_count()
        resident = 2.0 * layer_params / 4
        assert pipe.hbm_s == pytest.approx(
            3.0 * (m + 4 - 1) * resident / 8.19e11, rel=1e-6
        )
        # the floor binds the step estimate from below
        assert pipe.step_s >= pipe.hbm_s

    def test_prefer_breaks_ties(self):
        cfg = GPTConfig.tiny()
        (spec, _), *_ = search_spec(
            profile_of(cfg), 8, batch_size=8, hbm=HBM_16G,
            prefer=("fsdp",),
        )
        # tiny model: dp and dp/fsdp are within noise; prefer tips it.
        assert spec.fsdp > 1 or spec == ParallelSpec(data=8)


class TestStateBytes:
    def test_matches_actual_sharded_state(self):
        """The analytic per-device bytes must equal what GSPMD actually
        materializes (the whole point of computing it from the rules)."""
        cfg = dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32)
        model = GPT(cfg)
        opt = optax.adamw(1e-3)
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (8, 16), 0, cfg.vocab_size
        )

        def token_loss(module, params, b):
            return loss_fn(module.apply({"params": params}, b), b)

        spec = ParallelSpec(fsdp=8)

        def init_fn(r):
            variables = model.init(r, tokens)
            p = variables["params"]
            return {"params": p, "opt": opt.init(p), "step": 0}

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        predicted = state_bytes_per_device(abstract, spec)

        res = auto_accelerate(
            model, opt, tokens, token_loss, spec=spec
        )
        actual = sum(
            leaf.addressable_shards[0].data.nbytes
            for leaf in jax.tree_util.tree_leaves(res.state)
        )
        # ceil-div padding may overcount slightly; never undercount.
        assert predicted >= actual
        assert predicted <= actual * 1.05 + 4096

    def test_fsdp_halves_vs_coarser(self):
        cfg = GPTConfig.tiny()
        model = GPT(cfg)
        opt = optax.adamw(1e-3)
        tokens = jnp.zeros((8, 16), jnp.int32)

        def init_fn(r):
            variables = model.init(r, tokens)
            p = variables["params"]
            return {"params": p, "opt": opt.init(p), "step": 0}

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        b2 = state_bytes_per_device(abstract, ParallelSpec(fsdp=2))
        b8 = state_bytes_per_device(abstract, ParallelSpec(fsdp=8))
        assert b8 < b2


class TestReconfigure:
    def test_seq_spec_flips_to_ring(self):
        model = GPT(GPTConfig.tiny())
        out = reconfigure_module(model, ParallelSpec(seq=2))
        assert out.cfg.attn_impl == "ring"

    def test_pipe_spec_sets_stages(self):
        model = GPT(GPTConfig.tiny())
        out = reconfigure_module(model, ParallelSpec(pipe=2))
        assert out.cfg.pipeline_stages == 2

    def test_noop_returns_same_module(self):
        model = GPT(GPTConfig.tiny())
        assert reconfigure_module(model, ParallelSpec(data=8)) is model


class TestAutoIntegration:
    def test_auto_trains_tiny(self):
        """spec="auto" end-to-end through the search on the CPU mesh."""
        cfg = dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32)
        model = GPT(cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
        )

        def token_loss(module, params, b):
            return loss_fn(module.apply({"params": params}, b), b)

        res = auto_accelerate(
            model, optax.adamw(1e-3), tokens, token_loss, spec="auto"
        )
        assert res.spec.total == 8
        state = res.state
        batch = jax.device_put(tokens, res.batch_sharding)
        losses = []
        for _ in range(3):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        assert np.isfinite(losses).all()


class TestAllowTensorOptOut:
    def test_false_forbids_tensor_candidates(self):
        """allow_tensor=False must strip tensor from the search space
        even for config-carrying models (round-4 review finding)."""
        import optax

        cfg = dataclasses.replace(
            GPTConfig.tiny(), dtype=jnp.float32, num_heads=2
        )
        model = GPT(cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
        )

        def token_loss(module, params, b):
            return loss_fn(module.apply({"params": params}, b), b)

        res = auto_accelerate(
            model, optax.adamw(1e-3), tokens, token_loss, spec="auto",
            allow_tensor=False,
        )
        assert res.spec.tensor == 1


class TestHierarchyAwareness:
    """Multi-host cost model: axes whose collective block spans hosts
    are priced at DCN (canonical mesh layout, outer axes cross first) —
    the model that makes hierarchical placements win."""

    def _est(self, spec, cfg, dph, batch=16):
        return estimate(
            profile_of(cfg), spec, batch_size=batch, hbm=HBM_16G,
            devices_per_host=dph,
        )

    def test_crossing_axis_detection(self):
        from dlrover_tpu.accel.search import _axis_links

        # 16 devices, 8/host, canonical order data,fsdp,pipe,...,tensor
        cross = _axis_links(ParallelSpec(data=2, fsdp=8), 8)
        assert cross["data"] is True       # spans both hosts
        assert cross["fsdp"] is False      # inner block of 8 fits a host
        cross = _axis_links(ParallelSpec(fsdp=16), 8)
        assert cross["fsdp"] is True
        cross = _axis_links(ParallelSpec(pipe=2, tensor=8), 8)
        assert cross["pipe"] is True
        assert cross["tensor"] is False
        # single host: nothing crosses
        cross = _axis_links(ParallelSpec(fsdp=16), 0)
        assert not any(cross.values())

    def test_hierarchical_fsdp_beats_crossing_fsdp(self):
        # GPT-2-xl over 2 hosts x 8: fsdp gathers across DCN are ruinous;
        # dp-across-hosts + fsdp-inside must rank faster.
        cfg = GPTConfig.gpt2_xl()
        crossing = self._est(ParallelSpec(fsdp=16), cfg, dph=8)
        hier = self._est(ParallelSpec(data=2, fsdp=8), cfg, dph=8)
        assert hier.step_s < crossing.step_s
        # on ONE host the ordering flips or narrows: fsdp=16 is fine
        flat_crossing = self._est(ParallelSpec(fsdp=16), cfg, dph=0)
        assert flat_crossing.comm_s < crossing.comm_s

    def test_pp_is_the_cheap_axis_to_cross(self):
        # TP all-reduces over DCN vs PP boundary transfers over DCN:
        # at equal degrees the pipeline's per-microbatch activation
        # traffic must price far below host-crossing TP.
        cfg = GPTConfig(
            vocab_size=50264, max_seq_len=2048, num_layers=32,
            num_heads=32, d_model=4096, remat=True,
        )
        tp_cross = self._est(ParallelSpec(tensor=16), cfg, dph=8)
        pp_hier = self._est(
            ParallelSpec(pipe=2, tensor=8), cfg, dph=8, batch=16
        )
        assert pp_hier.step_s < tp_cross.step_s

    def test_search_picks_hierarchical_on_two_hosts(self):
        cfg = GPTConfig.gpt2_xl()
        ranked = search_spec(
            profile_of(cfg), 16, batch_size=16, hbm=HBM_16G,
            devices_per_host=8,
        )
        spec = ranked[0][0]
        assert spec.fsdp <= 8, f"host-crossing gathers chosen: {spec}"
        assert spec.total == 16


class TestProfiledSearch:
    # Promoted to slow for tier-1 headroom (~19s: compiles and times
    # K candidate meshes); the search logic itself stays tier-1 via
    # the non-profiled TestSearch cases.
    @pytest.mark.slow
    def test_dry_run_top_k_picks_and_trains(self):
        """spec="auto" + profile=True: the search's top-K candidates are
        compiled and timed on the real (virtual) mesh and the winner is
        built — the reference dry-runner path end-to-end."""
        cfg = dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32)
        model = GPT(cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
        )

        def token_loss(module, params, b):
            return loss_fn(module.apply({"params": params}, b), b)

        res = auto_accelerate(
            model, optax.adamw(1e-3), tokens, token_loss, spec="auto",
            profile=True, profile_steps=2, search_top_k=3,
        )
        assert res.spec.total == 8
        assert res.search_ranking is not None
        assert 1 <= len(res.search_ranking) <= 3
        state = res.state
        batch = jax.device_put(tokens, res.batch_sharding)
        losses = []
        for _ in range(3):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]


class TestCalibratedAgainstChip:
    """The cost model's constants must rest on
    measurements, not spec-sheet priors. Measured step times below are
    from an earlier on-chip run on one TPU v5e chip, to be re-measured;
    estimate() must predict each within +-30%. If a model
    or kernel change moves the real numbers, re-measure and update —
    this test pins the calibration contract, not the hardware."""

    PEAK = 197e12  # v5e bf16 (benchmark/peaks.json)

    # (config ctor kwargs, batch, measured step seconds)
    MEASURED = [
        # small: 124M, B=16, 93.2k tok/s -> 16*1024/93200
        (dict(vocab_size=50257, max_seq_len=1024, num_layers=12,
              num_heads=12, d_model=768, remat=True,
              remat_policy="dots"), 16, 16 * 1024 / 93200),
        # medium: 355M, B=8, 224.5 ms (r5 A/B/A probe)
        (dict(vocab_size=50257, max_seq_len=1024, num_layers=24,
              num_heads=16, d_model=1024, remat=True,
              remat_policy="dots"), 8, 0.2245),
        # gpt2-xl: 1.5B, B=4, 36.0% MFU
        (dict(vocab_size=50257, max_seq_len=1024, num_layers=48,
              num_heads=25, d_model=1600, remat=True), 4, None),
    ]

    def test_estimate_matches_measured_step_times(self):
        from dlrover_tpu.accel.search import estimate

        for kwargs, batch, measured in self.MEASURED:
            cfg = GPTConfig(**kwargs)
            p = profile_of(cfg)
            if measured is None:  # derive from recorded MFU
                flops = cfg.flops_per_token() * batch * cfg.max_seq_len
                measured = flops / (0.36 * self.PEAK)
            est = estimate(
                p, ParallelSpec(), batch_size=batch, hbm=HBM_16G,
                peak_flops=self.PEAK,
            )
            ratio = est.step_s / measured
            assert 0.7 < ratio < 1.3, (kwargs["d_model"], ratio)

    def test_llama_measured_within_band(self):
        from dlrover_tpu.accel.search import estimate
        from dlrover_tpu.models.llama import LlamaConfig

        # LLaMA 1.15B, B=4, S=2048: 12.7k tok/s (an earlier on-chip run, to be
        # re-measured)
        cfg = LlamaConfig(
            vocab_size=32000, max_seq_len=2048, num_layers=18,
            num_heads=16, num_kv_heads=8, d_model=2048, remat=True,
            remat_policy="dots",
        )
        measured = 4 * 2048 / 12700
        est = estimate(
            ModelProfile.from_config(cfg), ParallelSpec(),
            batch_size=4, hbm=HBM_16G, peak_flops=self.PEAK,
        )
        ratio = est.step_s / measured
        assert 0.7 < ratio < 1.35, ratio
