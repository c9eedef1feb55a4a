"""Acceleration-layer tests on the virtual 8-device CPU mesh.

Parity with the reference's strategy of testing TP/parallel numerics on
2-process gloo worlds (SURVEY.md §4.5) — here GSPMD shardings are validated
by comparing sharded training against the single-device baseline.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.accel import ParallelSpec, auto_accelerate, create_mesh
from dlrover_tpu.accel.accelerate import choose_spec
from dlrover_tpu.accel.mesh import MeshConfig
from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn


def token_loss(module, params, batch):
    return loss_fn(module.apply({"params": params}, batch), batch)


def tiny_cfg(**kw):
    return dataclasses.replace(
        GPTConfig.tiny(), dtype=jnp.float32, **kw
    )


def run_training(spec, steps=3, cfg=None):
    cfg = cfg or tiny_cfg()
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
    res.state = state  # the input state was donated; return the live one
    return losses, res


class TestMesh:
    def test_sizes_and_wildcard(self):
        mesh = create_mesh([("data", -1), ("tensor", 2)])
        assert mesh.shape["data"] == 4
        assert mesh.shape["tensor"] == 2

    def test_canonical_axis_order(self):
        mesh = create_mesh([("tensor", 2), ("data", 2), ("fsdp", 2)])
        assert mesh.axis_names == ("data", "fsdp", "tensor")

    def test_bad_sizes_raise(self):
        with pytest.raises(ValueError):
            MeshConfig([("data", 3)]).resolved(8)
        with pytest.raises(ValueError):
            MeshConfig([("data", -1), ("fsdp", -1)]).resolved(8)


class TestShardedNumerics:
    """Every strategy must train identically to the 1-device baseline."""

    @pytest.fixture(scope="class")
    def baseline(self):
        return run_training(ParallelSpec())[0]

    @pytest.mark.parametrize(
        "spec",
        [
            ParallelSpec(data=8),
            ParallelSpec(fsdp=8),
            ParallelSpec(data=2, fsdp=4),
            ParallelSpec(data=2, fsdp=2, tensor=2),
            ParallelSpec(data=2, fsdp=4, zero=True),
        ],
        ids=["dp", "fsdp-zero3", "dp-fsdp", "dp-fsdp-tp", "dp-fsdp-zero1"],
    )
    def test_matches_baseline(self, spec, baseline):
        losses, res = run_training(spec)
        np.testing.assert_allclose(losses, baseline, rtol=2e-5, atol=2e-5)

    def test_fsdp_actually_shards_params(self):
        _, res = run_training(ParallelSpec(fsdp=8), steps=1)
        # The embedding table's `embed` (d_model) dim is sharded over the
        # fsdp axis: each device holds 1/8 of the columns.
        emb = res.state["params"]["wte"]["embedding"]
        shard = emb.addressable_shards[0]
        assert shard.data.shape[1] == emb.shape[1] // 8

    def test_tp_shards_mlp(self):
        _, res = run_training(
            ParallelSpec(tensor=2), steps=1,
            cfg=tiny_cfg(scan_layers=False),
        )
        kernel = res.state["params"]["block_0"]["up"]["kernel"]
        shard = kernel.addressable_shards[0]
        assert shard.data.shape[-1] == kernel.shape[-1] // 2

    def test_opt_state_sharded_like_params(self):
        """ZeRO for free: adam mu mirrors the param sharding."""
        _, res = run_training(ParallelSpec(fsdp=8), steps=1)
        mu_emb = res.state["opt"][0].mu["wte"]["embedding"]
        emb = res.state["params"]["wte"]["embedding"]
        assert mu_emb.sharding == emb.sharding


FSDP_SPECS = [
    ParallelSpec(fsdp=4),
    ParallelSpec(fsdp=8),
    ParallelSpec(data=2, fsdp=2, tensor=2),
    ParallelSpec(data=2, fsdp=4, zero=True),
]
FSDP_IDS = ["fsdp4", "fsdp8", "dp-fsdp-tp", "dp-fsdp-zero1"]


def _axes(entry):
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def _boxed_params():
    """Path -> logical names of the tiny GPT's parameters."""
    tokens = jnp.zeros((8, 16), jnp.int32)
    boxed = jax.eval_shape(
        lambda r: GPT(tiny_cfg()).init(r, tokens)["params"],
        jax.random.PRNGKey(0),
    )
    return {
        jax.tree_util.keystr(path): tuple(leaf.names)
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            boxed, is_leaf=lambda x: hasattr(x, "names")
        )[0]
    }


def _vector_a_layer(names):
    return sum(n != "layers" for n in names) == 1


class TestFsdpKeepsVectorsWhole:
    """Under fsdp a leaf that is one vector a layer (LayerNorm scale and
    bias, every projection's bias) is not sharded over the fsdp axis;
    every other leaf keeps the axes its logical names resolve to."""

    @pytest.mark.parametrize("spec", FSDP_SPECS, ids=FSDP_IDS)
    def test_vectors_leave_the_fsdp_axis_and_matrices_keep_it(self, spec):
        _, res = run_training(spec, steps=1)
        names = _boxed_params()
        flat = jax.tree_util.tree_flatten_with_path(res.state["params"])[0]
        assert len(flat) == len(names) == 16
        for path, leaf in flat:
            key = jax.tree_util.keystr(path)
            placed = [_axes(e) for e in leaf.sharding.spec]
            used = {a for axes in placed for a in axes}
            if _vector_a_layer(names[key]):
                assert "fsdp" not in used, key
                # whole on every chip but for the tensor split of heads/mlp
                div = spec.tensor if "tensor" in used else 1
                shard = leaf.addressable_shards[0].data
                assert shard.shape[-1] == leaf.shape[-1] // div, key
            elif "embed" in names[key]:
                assert "fsdp" in used, key     # the matrices as before

    def test_the_biases_and_norms_are_whole_under_fsdp4(self):
        _, res = run_training(ParallelSpec(fsdp=4), steps=1)
        params = res.state["params"]["blocks"]
        for leaf in (params["qkv"]["bias"], params["up"]["bias"],
                     params["ln1"]["scale"], params["down"]["bias"]):
            assert leaf.addressable_shards[0].data.shape == leaf.shape
        kernel = params["up"]["kernel"]              # [layers, embed, mlp]
        assert kernel.addressable_shards[0].data.shape[1] == (
            kernel.shape[1] // 4
        )

    def test_opt_state_of_a_vector_is_whole_like_its_param(self):
        _, res = run_training(ParallelSpec(fsdp=8), steps=1)
        bias = res.state["params"]["blocks"]["qkv"]["bias"]
        norm = res.state["params"]["blocks"]["ln1"]["scale"]
        mu = res.state["opt"][0].mu["blocks"]
        assert mu["qkv"]["bias"].sharding == bias.sharding
        assert mu["ln1"]["scale"].sharding == norm.sharding
        shard = mu["ln1"]["scale"].addressable_shards[0].data
        assert shard.shape == norm.shape

    @pytest.mark.parametrize("spec", FSDP_SPECS, ids=FSDP_IDS)
    def test_state_bytes_still_match_what_is_placed(self, spec):
        """The search's per-device bytes place the vectors as the build
        does: never under what the devices hold."""
        from dlrover_tpu.accel.search import state_bytes_per_device

        _, res = run_training(spec, steps=1)
        cfg, opt = tiny_cfg(), optax.adamw(1e-3)
        tokens = jnp.zeros((8, 16), jnp.int32)

        def init_fn(r):
            p = GPT(cfg).init(r, tokens)["params"]
            return {"params": p, "opt": opt.init(p),
                    "step": jnp.zeros((), jnp.int32)}

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        predicted = state_bytes_per_device(abstract, spec)
        actual = sum(
            leaf.addressable_shards[0].data.nbytes
            for leaf in jax.tree_util.tree_leaves(res.state)
        )
        assert actual <= predicted <= actual * 1.05 + 4096


class TestReplicatedParamsCounter:
    @pytest.fixture
    def tracer(self, monkeypatch):
        from dlrover_tpu.utils import tracing

        fresh = tracing.Tracer()
        monkeypatch.setattr(tracing, "_tracer", fresh)
        return fresh

    @staticmethod
    def _counted(tracer):
        return [e["args"] for e in tracer.events
                if e["name"] == "accel.replicated_params"]

    def test_counts_the_leaves_and_bytes_fsdp_keeps_whole(self, tracer):
        run_training(ParallelSpec(fsdp=8), steps=1)
        # 8 vectors a layer (two LayerNorms, four biases) stacked over the
        # layers, and the final LayerNorm's two: 2 x (4 x 32 + 96 + 32 +
        # 128 + 32) + 2 x 32 float32 values
        assert self._counted(tracer)[-1] == {
            "kind=leaves": 10, "kind=bytes": 3584,
        }

    def test_a_tensor_split_vector_is_not_whole(self, tracer):
        run_training(ParallelSpec(data=2, fsdp=2, tensor=2), steps=1)
        # qkv/bias and up/bias keep their heads/mlp split over tensor
        assert self._counted(tracer)[-1] == {
            "kind=leaves": 8, "kind=bytes": 3584 - 4 * 2 * (96 + 128),
        }

    def test_raised_only_under_fsdp(self, tracer):
        run_training(ParallelSpec(data=8), steps=1)
        assert self._counted(tracer) == []

    def test_in_the_span_table(self):
        from dlrover_tpu.utils import tracing

        layer, thread, covers = tracing.SPANS["accel.replicated_params"]
        assert layer == "accel" and "kind=leaves" in covers


class TestAutoStrategy:
    def test_small_model_pure_dp(self):
        spec = choose_spec(param_count=10_000_000, n_devices=8, hbm=16e9)
        assert spec == ParallelSpec(data=8)

    def test_large_model_gets_fsdp(self):
        # 10B params * 16B = 160GB state; 16GB chips need fsdp.
        spec = choose_spec(param_count=10_000_000_000, n_devices=8, hbm=16e9)
        assert spec.fsdp > 1
        assert spec.total == 8

    def test_auto_end_to_end(self):
        losses, res = run_training("auto")
        assert res.spec.data == 8  # tiny model -> pure DP
        assert losses[-1] < losses[0]

    def test_remat_variant_trains(self):
        losses, _ = run_training(
            ParallelSpec(data=4), cfg=tiny_cfg(remat=True)
        )
        assert losses[-1] < losses[0]
