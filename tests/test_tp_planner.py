"""Automatic TP placement tests (parity:
``atorch/atorch/auto/opt_lib/shard_planners/mip_tp_planner.py``).

A plain flax model with ZERO sharding annotations must get Megatron-
correct column/row TP placement from one abstract trace — and train
identically to the single-device baseline under ``tensor > 1``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
import flax.linen as nn

from dlrover_tpu.accel import ParallelSpec, auto_accelerate
from dlrover_tpu.accel.tp_planner import plan_tp


class PlainBlock(nn.Module):
    """Unannotated pre-LN transformer block: separate q/k/v (square
    kernels — only dataflow can classify them)."""

    d: int = 32
    heads: int = 4

    @nn.compact
    def __call__(self, x):
        y = nn.LayerNorm(name="ln1")(x)
        q = nn.Dense(self.d, name="q_proj")(y)
        k = nn.Dense(self.d, name="k_proj")(y)
        v = nn.Dense(self.d, name="v_proj")(y)
        b, s, d = x.shape
        hd = d // self.heads
        qh = q.reshape(b, s, self.heads, hd)
        kh = k.reshape(b, s, self.heads, hd)
        vh = v.reshape(b, s, self.heads, hd)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(hd)
        mask = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(
            jnp.where(mask, logits, -1e9), axis=-1
        )
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, vh).reshape(b, s, d)
        x = x + nn.Dense(self.d, name="o_proj")(attn)
        y = nn.LayerNorm(name="ln2")(x)
        y = nn.gelu(nn.Dense(4 * self.d, name="up")(y))
        return x + nn.Dense(self.d, name="down")(y)


class PlainLM(nn.Module):
    vocab: int = 128
    d: int = 32
    layers: int = 2

    @nn.compact
    def __call__(self, tokens):
        x = nn.Embed(self.vocab, self.d, name="wte")(tokens)
        for i in range(self.layers):
            x = PlainBlock(d=self.d, name=f"block_{i}")(x)
        return nn.Dense(self.vocab, name="lm_head")(x)


class GQABlock(nn.Module):
    """Unannotated GQA block: k/v are *contractions*
    (out = kv_heads * head_dim < d) that the width rule alone would
    misclassify row-parallel; only the shared-input sibling rule puts
    them in the q column group."""

    d: int = 32
    heads: int = 4
    kv_heads: int = 2

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        hd = d // self.heads
        y = nn.LayerNorm(name="ln1")(x)
        q = nn.Dense(self.d, name="q_proj")(y)
        k = nn.Dense(self.kv_heads * hd, name="k_proj")(y)
        v = nn.Dense(self.kv_heads * hd, name="v_proj")(y)
        qh = q.reshape(b, s, self.heads, hd)
        kh = k.reshape(b, s, self.kv_heads, hd)
        vh = v.reshape(b, s, self.kv_heads, hd)
        rep = self.heads // self.kv_heads
        kh = jnp.repeat(kh, rep, axis=2)
        vh = jnp.repeat(vh, rep, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(hd)
        mask = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(mask, logits, -1e9), axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, vh).reshape(b, s, d)
        x = x + nn.Dense(self.d, name="o_proj")(attn)
        y = nn.LayerNorm(name="ln2")(x)
        gate = nn.Dense(4 * self.d, name="gate")(y)
        up = nn.Dense(4 * self.d, name="up")(y)
        return x + nn.Dense(self.d, name="down")(nn.silu(gate) * up)


class GQALM(nn.Module):
    vocab: int = 128
    d: int = 32
    layers: int = 2

    @nn.compact
    def __call__(self, tokens):
        x = nn.Embed(self.vocab, self.d, name="wte")(tokens)
        for i in range(self.layers):
            x = GQABlock(d=self.d, name=f"block_{i}")(x)
        return nn.Dense(self.vocab, name="lm_head")(x)


def plan_roles(reg):
    """Map path -> axes from the registry's explicit rules."""
    return {
        pat.pattern: axes for pat, axes in reg._rules
    }


class TestClassification:
    @pytest.fixture(scope="class")
    def registry(self):
        model = PlainLM()
        tokens = jnp.zeros((2, 8), jnp.int32)
        return plan_tp(
            model, jax.random.PRNGKey(0), tokens, vocab_size=128
        )

    def test_qkv_siblings_are_column(self, registry):
        rules = plan_roles(registry)
        for proj in ("q_proj", "k_proj", "v_proj"):
            key = f"^block_0/{proj}/kernel$"
            assert rules[key] == ("embed", "mlp"), (proj, rules.get(key))

    def test_o_proj_is_row(self, registry):
        rules = plan_roles(registry)
        assert rules["^block_0/o_proj/kernel$"] == ("mlp", "embed")

    def test_mlp_pair(self, registry):
        rules = plan_roles(registry)
        assert rules["^block_0/up/kernel$"] == ("embed", "mlp")
        assert rules["^block_0/down/kernel$"] == ("mlp", "embed")

    def test_lm_head_vocab_sharded(self, registry):
        rules = plan_roles(registry)
        assert rules["^lm_head/kernel$"] == ("embed", "vocab")

    def test_row_bias_replicated_col_bias_sharded(self, registry):
        rules = plan_roles(registry)
        assert rules["^block_0/o_proj/bias$"] == (None,)
        assert rules["^block_0/up/bias$"] == ("mlp",)

    def test_norms_never_planned(self, registry):
        """LayerNorm is a width-preserving __call__ but owns no kernel:
        it must not register rules (or worse, satisfy the square-closer
        heuristic in place of o_proj)."""
        for pat in plan_roles(registry):
            assert "/ln1/" not in pat and "/ln2/" not in pat


class TestGQAClassification:
    """GQA: k/v projections are contractions yet must be column-parallel
    (sharded over kv heads) to compose with head-sharded attention."""

    @pytest.fixture(scope="class")
    def registry(self):
        model = GQALM()
        tokens = jnp.zeros((2, 8), jnp.int32)
        return plan_tp(
            model, jax.random.PRNGKey(0), tokens, vocab_size=128
        )

    def test_gqa_kv_are_column_not_row(self, registry):
        rules = plan_roles(registry)
        for proj in ("q_proj", "k_proj", "v_proj"):
            key = f"^block_0/{proj}/kernel$"
            assert rules[key] == ("embed", "mlp"), (proj, rules.get(key))

    def test_o_proj_still_row_closer(self, registry):
        rules = plan_roles(registry)
        assert rules["^block_0/o_proj/kernel$"] == ("mlp", "embed")

    def test_swiglu_pair(self, registry):
        rules = plan_roles(registry)
        assert rules["^block_0/gate/kernel$"] == ("embed", "mlp")
        assert rules["^block_0/up/kernel$"] == ("embed", "mlp")
        assert rules["^block_0/down/kernel$"] == ("mlp", "embed")

    def test_singleton_contraction_not_pulled_into_group(self):
        """A d->1 value head sharing its input with the LM head must NOT
        be column-sharded (its output dim can't divide a tensor axis) —
        only twin contractions (GQA k/v) outrank the width rule."""

        class TwoHeads(nn.Module):
            @nn.compact
            def __call__(self, tokens):
                x = nn.Embed(128, 32, name="wte")(tokens)
                lm = nn.Dense(128, name="lm_head")(x)
                value = nn.Dense(1, name="value_head")(x)
                return lm, value

        reg = plan_tp(
            TwoHeads(), jax.random.PRNGKey(0),
            jnp.zeros((2, 8), jnp.int32), vocab_size=128,
        )
        rules = plan_roles(reg)
        assert rules["^value_head/kernel$"] == ("mlp", "embed")  # row
        assert rules["^lm_head/kernel$"] == ("embed", "vocab")


class TestPlannedTraining:
    def loss(self, module, params, batch):
        logits = module.apply({"params": params}, batch)
        logits = logits[:, :-1].astype(jnp.float32)
        targets = batch[:, 1:]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, targets[..., None], axis=-1
        )[..., 0]
        return jnp.mean(lse - tgt)

    def run(self, spec, allow_tensor=False, model_cls=PlainLM):
        model = model_cls()
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 8), 0, 128
        )
        res = auto_accelerate(
            model, optax.adamw(1e-3), tokens, self.loss, spec=spec,
            allow_tensor=allow_tensor,
        )
        state = res.state
        batch = jax.device_put(tokens, res.batch_sharding)
        losses = []
        for _ in range(3):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        res.state = state  # input state was donated; hand back the live one
        return losses, res

    def test_tp_matches_baseline(self):
        base, _ = self.run(ParallelSpec())
        tp, res = self.run(ParallelSpec(tensor=2), allow_tensor=True)
        np.testing.assert_allclose(tp, base, rtol=2e-5, atol=2e-5)

    def test_planned_kernels_actually_sharded(self):
        _, res = self.run(
            ParallelSpec(data=2, tensor=2), allow_tensor=True
        )
        up = res.state["params"]["block_0"]["up"]["kernel"]
        shard = up.addressable_shards[0]
        assert shard.data.shape[-1] == up.shape[-1] // 2  # col sharded
        down = res.state["params"]["block_0"]["down"]["kernel"]
        shard = down.addressable_shards[0]
        assert shard.data.shape[0] == down.shape[0] // 2  # row sharded

    def test_gqa_tp_matches_baseline(self):
        """The GQA plan (k/v column over kv heads) trains TP=2 to
        numerics parity with the single-device baseline."""
        base, _ = self.run(ParallelSpec(), model_cls=GQALM)
        tp, res = self.run(
            ParallelSpec(tensor=2), allow_tensor=True, model_cls=GQALM
        )
        np.testing.assert_allclose(tp, base, rtol=2e-5, atol=2e-5)
        kv = res.state["params"]["block_0"]["k_proj"]["kernel"]
        shard = kv.addressable_shards[0]
        assert shard.data.shape[-1] == kv.shape[-1] // 2  # col sharded

    def test_dp_fsdp_tp_composition(self):
        base, _ = self.run(ParallelSpec())
        mixed, _ = self.run(
            ParallelSpec(data=2, fsdp=2, tensor=2), allow_tensor=True
        )
        np.testing.assert_allclose(mixed, base, rtol=2e-5, atol=2e-5)
