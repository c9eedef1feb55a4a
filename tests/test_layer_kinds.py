"""Layers of more than one kind in one ``models/llama.py`` stack: sliding
and position-free attention by layer, a dense FFN in the leading layer and
held experts after it, normed q and k, a gated attention output, sandwich
norms and a scaled embedding, against the plain reference written from the
architecture's equations (``benchmark/reference/trinity.py``); the stacks
of one kind built as they always were; and the step's second output, from
the loss through ``make_train_step`` to the trainer's counters."""

import dataclasses
import hashlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import cells
from benchmark.reference import common
from dlrover_tpu.accel import ParallelSpec, auto_accelerate
from dlrover_tpu.models.llama import (
    Llama,
    LlamaBlock,
    LlamaConfig,
    counted_loss_fn,
    loss_fn,
)
from dlrover_tpu.ops.moe import COUNTERS, HeldExperts
from dlrover_tpu.utils import tracing

CELL = "trinity-large.train16k"
SEQ = 128


def _toy():
    """The cell's rehearsal: toy widths, 16 experts of which 8 held, a
    window of 32, five layers (dense + sliding, sliding, sliding, full,
    sliding)."""
    cell = cells.resolve(CELL, cells.ROOT, rehearsal=True)
    job = dict(cell["job"], param_dtype="float32", compute_dtype="float32",
               sequence=SEQ, remat=None,
               attention={"impl": "xla"})
    return cell["config"], job


def _family(kind):
    return cells.family_module(kind, "trinity")


@pytest.fixture(scope="module")
def built():
    config, job = _toy()
    out = _family("models").build(config, job)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0,
                                config["vocab_size"])
    params = nn.meta.unbox(
        out["module"].init(jax.random.PRNGKey(0), tokens)
    )["params"]
    return {**out, "config": config, "job": job, "tokens": tokens,
            "params": params}


class TestTheConfiguration:
    def test_kinds_by_layer(self, built):
        cfg = built["cfg"]
        assert cfg.attn_kinds == ("sliding",) * 3 + ("nope", "sliding")
        assert [cfg.routed(i) for i in range(5)] == [False] + [True] * 4
        kinds = cfg.layer_kinds()
        assert kinds[0] == {"attn_kind": "sliding", "routed": False}
        assert kinds[3] == {"attn_kind": "nope", "routed": True}
        assert LlamaConfig.tiny().layer_kinds() is None

    def test_parameter_trees_and_counts(self, built):
        params, cfg = built["params"], built["cfg"]
        assert set(params) == {"embed", "final_norm", "lm_head"} | {
            f"layer_{i}" for i in range(5)
        }
        assert "gate_proj" in params["layer_0"]
        assert "experts" not in params["layer_0"]
        assert set(params["layer_1"]["experts"]) == {
            "router", "w_gate", "w_up", "w_down", "shared_gate",
            "shared_up", "shared_down",
        }
        assert {"q_norm", "k_norm", "attn_gate", "attn_post_norm",
                "mlp_post_norm"} <= set(params["layer_3"])
        count = sum(x.size for x in jax.tree_util.tree_leaves(params))
        assert count == cfg.param_count()
        assert count == _family("models").sizes(built["config"])["params"]
        assert cfg.active_param_count() < cfg.param_count()

    def test_pairs_and_flops_follow_each_layers_own_mask(self, built):
        cfg = built["cfg"]
        w = cfg.attn_window
        sliding = w * (w + 1) // 2 + (SEQ - w) * w
        assert [cfg.attention_pairs(i) for i in range(5)] == (
            [sliding] * 3 + [SEQ * (SEQ + 1) // 2, sliding]
        )
        pairs = 4 * sliding + SEQ * (SEQ + 1) // 2
        assert cfg.flops_per_token() == pytest.approx(
            6 * cfg.active_param_count()
            + 12 * cfg.num_heads * cfg.head_dim * pairs / SEQ
        )
        plain = LlamaConfig.tiny()
        assert plain.attention_pairs() == plain.max_seq_len ** 2
        assert plain.flops_per_token() == pytest.approx(
            6 * plain.param_count()
            + 12 * plain.num_layers * plain.d_model * plain.max_seq_len
        )

    @pytest.mark.parametrize("change", [
        {"attn_kinds": ("full",)}, {"attn_kinds": ("full", "round")},
        {"attn_kinds": ("full", "sliding")},
        {"attn_kinds": ("full", "full"), "mixer": "eva", "attn_window": 8,
         "attn_chunk": 4},
        {"num_experts": 4, "experts": HeldExperts(
            routed=8, held=8, per_token=2, ff_dim=8, pair_buffer=64)},
    ])
    def test_a_configuration_that_says_nothing_is_refused(self, change):
        with pytest.raises(ValueError):
            dataclasses.replace(LlamaConfig.tiny(), **change)

    def test_layers_of_one_kind_still_stack_under_scan(self):
        cfg = dataclasses.replace(
            LlamaConfig.tiny(), attn_kinds=("sliding", "sliding"),
            attn_window=16,
        )
        tokens = jnp.zeros((1, 32), jnp.int32)
        shapes = jax.eval_shape(
            lambda: Llama(cfg).init(jax.random.PRNGKey(0), tokens)
        )["params"]
        assert "layers" in shapes and "layer_0" not in shapes
        mixed = dataclasses.replace(cfg, attn_kinds=("sliding", "nope"))
        shapes = jax.eval_shape(
            lambda: Llama(mixed).init(jax.random.PRNGKey(0), tokens)
        )["params"]
        assert {"layer_0", "layer_1"} <= set(shapes)


class TestAgainstTheReference:
    """float32 compute under ``highest``: what is left is the order of
    sums."""

    @pytest.mark.parametrize("layer", [0, 1, 3])
    def test_a_block_of_each_kind(self, built, layer):
        cfg, config = built["cfg"], built["config"]
        family = _family("models")
        x = jax.random.normal(jax.random.PRNGKey(layer), (2, SEQ, cfg.d_model))
        block = LlamaBlock(cfg, **cfg.layer_kinds()[layer])
        params = built["params"][f"layer_{layer}"]
        as_reference = family.to_reference({
            **built["params"], "layer_0": built["params"]["layer_0"],
            "layer_1": params if layer else built["params"]["layer_1"],
        })["layers"]
        prefix = "e." if layer else "d."
        p = {k[2:]: w[0] for k, w in as_reference.items()
             if k.startswith(prefix)}
        with jax.default_matmul_precision("highest"):
            got, counters = block.apply({"params": params}, x)
            want = _family("reference").layer(
                x, p, config["layer_types"][layer], config
            )
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert (counters is None) == (layer == 0)

    def test_the_whole_model_loss_and_gradients(self, built):
        family, config = _family("models"), built["config"]
        module, loss, params = built["module"], built["loss"], built["params"]
        tokens = built["tokens"]
        per_seq, ref_grads = common.loss_and_grads(
            _family("reference"), family.to_reference(params), tokens, config
        )
        with jax.default_matmul_precision("highest"):
            (value, counters), grads = jax.value_and_grad(
                lambda p: loss.with_metrics(module, p, tokens), has_aux=True
            )(params)
        assert float(value) == pytest.approx(float(per_seq.mean()), abs=2e-6)
        assert counters["moe.pairs{kind=overflowed}"] == 0
        got = family.to_reference(grads)
        np.testing.assert_allclose(got["embed"], ref_grads["embed"],
                                   atol=2e-6)
        for name, want in ref_grads["layer0"].items():
            np.testing.assert_allclose(
                got["layers"][name][0], want, atol=5e-6, err_msg=name
            )

    def test_the_kernels_and_remat_compute_the_same_model(self, built):
        config, job = _toy()
        job = dict(job, remat="dots", attention={
            "impl": "pallas", "block_q": 32, "block_k": 32})
        kernels = _family("models").build(config, job)
        tokens, params = built["tokens"], built["params"]
        with jax.default_matmul_precision("highest"):
            want, want_grads = jax.value_and_grad(
                lambda p: built["loss"](built["module"], p, tokens))(params)
            got, grads = jax.value_and_grad(
                lambda p: kernels["loss"](kernels["module"], p, tokens)
            )(params)
        assert float(got) == pytest.approx(float(want), abs=2e-6)
        for a, b in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want_grads)):
            np.testing.assert_allclose(a, b, atol=5e-6)

    def test_an_overflow_leaves_no_finite_loss(self, built):
        config, job = _toy()
        job = dict(job, moe={"pair_buffer": 64})
        tight = _family("models").build(config, job)
        value, counters = tight["loss"].with_metrics(
            tight["module"], built["params"], built["tokens"]
        )
        assert counters["moe.pairs{kind=overflowed}"] > 0
        assert not np.isfinite(value)
        assert not np.isfinite(
            tight["loss"](tight["module"], built["params"], built["tokens"])
        )


# ---------------------------------- the stacks of one kind, as they were

# sha256 of the parameter tree's shapes and of the loss gradient's jaxpr
# (addresses blanked) of the two other families' rehearsal builds. The
# trees are those of the commit before the layer kinds came (b341a3b,
# PR 33), and so were the jaxprs until PR 37 rewrote the flash kernels,
# whose bodies and prefetched schedules are part of the text: taken anew
# on that PR's tree (before: 108188372fa9ca40, f583c5b56df18459).
AS_THEY_WERE = {
    "mistral-7b.long16k": ("c14dcd83429f0c1b", "bf3b5ac976263772"),
    "evabyte.train32k": ("3c3f9ed166d6d098", "42326445823bb66c"),
}


@pytest.mark.parametrize("cell_name", sorted(AS_THEY_WERE))
def test_the_other_families_trees_and_jaxprs_are_unchanged(cell_name):
    cell = cells.resolve(cell_name, cells.ROOT, rehearsal=True)
    family = cells.family_module("models", cell["family"])
    out = family.build(cell["config"], cell["job"])
    module, loss = out["module"], out["loss"]
    tokens = jnp.zeros((1, 64), jnp.int32)
    params = nn.meta.unbox(jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), tokens)
    ))["params"]
    tree = str(jax.tree_util.tree_map(
        lambda x: (x.shape, str(x.dtype)), params
    ))
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(
        jax.grad(lambda p: loss(module, p, tokens))
    )(params)))
    digest = lambda s: hashlib.sha256(s.encode()).hexdigest()[:16]
    assert (digest(tree), digest(text)) == AS_THEY_WERE[cell_name]


# ------------------------------------------- the step's second output

def _scalar_loss(module, params, batch):
    return loss_fn(module.apply({"params": params}, batch)[0], batch)


def _counted_loss(module, params, batch):
    return counted_loss_fn(module.apply({"params": params}, batch), batch)


class _BothForms:
    """A scalar when called, ``(scalar, metrics)`` as ``with_metrics``."""

    __call__ = staticmethod(_scalar_loss)
    with_metrics = staticmethod(_counted_loss)


@pytest.fixture(scope="module")
def small():
    cfg = LlamaConfig(
        vocab_size=64, max_seq_len=32, num_layers=2, num_heads=2,
        d_model=16, d_ff=32, attn_kinds=("sliding", "nope"), attn_window=8,
        dense_layers=1, dtype=jnp.float32,
        experts=HeldExperts(routed=8, held=4, per_token=2, ff_dim=8,
                            pair_buffer=512, shared_ff_dim=8),
    )
    tokens = jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, 64)
    return Llama(cfg), tokens


class TestTheStepsSecondOutput:
    @pytest.mark.parametrize("grad_accum", [1, 2])
    def test_scalar_and_counted_losses_through_make_train_step(
        self, small, grad_accum
    ):
        module, tokens = small
        steps = {}
        for name, loss in (("scalar", _scalar_loss),
                           ("counted", _counted_loss),
                           ("both", _BothForms())):
            res = auto_accelerate(
                module, optax.sgd(0.1), tokens, loss,
                spec=ParallelSpec(data=1), grad_accum=grad_accum,
                rng=jax.random.PRNGKey(0),
            )
            batch = jax.device_put(tokens, res.batch_sharding)
            state, metrics = res.train_step(res.state, batch)
            steps[name] = (state, metrics)
        assert set(steps["scalar"][1]) == {"loss"}
        for name in ("counted", "both"):
            state, metrics = steps[name]
            assert set(metrics) == {"loss", *COUNTERS}
            assert metrics["moe.pairs{kind=buffer}"] == 512
            assert float(metrics["loss"]) == pytest.approx(
                float(steps["scalar"][1]["loss"]), rel=1e-6
            )
            for a, b in zip(jax.tree_util.tree_leaves(state["params"]),
                            jax.tree_util.tree_leaves(
                                steps["scalar"][0]["params"])):
                np.testing.assert_allclose(a, b, atol=1e-7)

    def test_a_metric_named_loss_is_refused(self, small):
        module, tokens = small

        def loss(module, params, batch):
            return _scalar_loss(module, params, batch), {"loss": 0.0}

        res = auto_accelerate(module, optax.sgd(0.1), tokens, loss,
                              spec=ParallelSpec(data=1))
        with pytest.raises(ValueError, match="may not be named"):
            res.train_step(res.state, tokens)

    @pytest.mark.parametrize("pipeline", [True, False])
    def test_the_trainer_raises_them_as_counters(self, small, monkeypatch,
                                                 pipeline):
        from dlrover_tpu.train.trainer import Trainer

        fresh = tracing.Tracer()
        monkeypatch.setattr(tracing, "_tracer", fresh)
        module, tokens = small
        trainer = Trainer(
            module, optax.sgd(0.1), _counted_loss, tokens,
            spec=ParallelSpec(data=1), report_metrics=False,
            rng=jax.random.PRNGKey(0),
        )
        result = trainer.fit(iter([tokens] * 3), steps=3, pipeline=pipeline)
        assert result["step"] == 3
        evaluated = trainer.evaluate(iter([tokens]))
        assert np.isfinite(evaluated["eval_loss"])
        raised = [e for e in fresh.events if e.get("ph") == "C"]
        pairs = [e["args"] for e in raised if e["name"] == "moe.pairs"][-1]
        # the pipelined loop reads a step's counters while the next runs:
        # the last step's are still on the device when fit returns
        reported = 2 if pipeline else 3
        assert pairs["kind=buffer"] == reported * 512
        assert 0 < pairs["kind=held"] < pairs["kind=buffer"]
        assert "kind=overflowed" not in pairs
        loads = [e["args"]["value"] for e in raised
                 if e["name"] == "moe.load_max_over_mean"]
        assert len(loads) == reported and loads[0] >= 1
        for name in ("moe.pairs", "moe.load_max_over_mean"):
            assert name in tracing.SPANS
