"""What a remat'ed block keeps of its flash-attention call: the forward
kernel's two outputs carry names (``ops.attention.RESIDUAL_NAMES``) that
the ``dots`` and ``dots_lite`` policies of ``models/stack.remat_policy``
save, so the forward kernel runs once a layer; ``nothing`` and
``offload`` run it again in the backward pass, as they did. Counted as
``pallas_call``s in the gradient's jaxpr; the gradients are the same
bits either way; the ``attn.residuals`` counter says which. Toy sizes,
interpret mode."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import gpt, llama, stack
from dlrover_tpu.ops.attention import (
    RESIDUAL_NAMES,
    AttentionMask,
    flash_attention,
)
from dlrover_tpu.ops.eva import eva_mask
from dlrover_tpu.utils import tracing

B, S, H, D = 2, 64, 4, 8
TOKENS = jnp.arange(B * S).reshape(B, S) % 40
# The forward kernel writes ``o`` [B, S, H, D] and one float32 a row.
RESIDUAL_BYTES = B * S * H * (D * 4 + 4)

EVA = dict(mixer="eva", attn_window=32, attn_chunk=8)
FAMILIES = {
    "gpt": (gpt.GPT, gpt.GPTConfig, {}),
    "llama": (llama.Llama, llama.LlamaConfig, {"d_ff": 64}),
    "llama-eva": (llama.Llama, llama.LlamaConfig, {"d_ff": 64, **EVA}),
}
# policy (None: no remat) -> pallas_calls in the gradient of two scanned
# layers: forward, dq, dkv, and the forward again where it is rerun.
CALLS = {"dots": 3, "dots_lite": 3, "nothing": 4, "offload": 4, None: 3}


def _model(family, policy, **more):
    module, config, extra = FAMILIES[family]
    base = dict(
        vocab_size=40, max_seq_len=S, num_layers=2, num_heads=H,
        d_model=H * D, dtype=jnp.float32, attn_impl="pallas",
        attn_block_q=16, attn_block_k=16, remat=policy is not None,
        remat_policy=policy or "nothing",
    )
    return module(config(**{**base, **extra, **more}))


def _params(model):
    return nn.meta.unbox(model.init(jax.random.PRNGKey(0), TOKENS)["params"])


def _loss(model):
    def loss(params):
        logits = model.apply({"params": params}, TOKENS)
        return gpt.loss_fn(logits[..., :40], TOKENS)

    return loss


def _count(jaxpr, primitive="pallas_call"):
    """Equations of ``primitive`` in a jaxpr and every jaxpr inside it
    (scan and remat bodies, custom-vjp calls, shard_map)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count(sub, primitive)
    return n


def _bits(tree):
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(tree)]


@pytest.fixture
def tracer(monkeypatch):
    fresh = tracing.Tracer()
    monkeypatch.setattr(tracing, "_tracer", fresh)
    return fresh


def _residual_events(tracer):
    return [e["args"] for e in tracer.events if e["name"] == "attn.residuals"]


class TestHowOftenTheForwardKernelRuns:
    @pytest.mark.parametrize("policy", list(CALLS), ids=str)
    @pytest.mark.parametrize("family", ["gpt", "llama"])
    def test_pallas_calls_in_the_gradient_of_two_scanned_layers(
        self, family, policy
    ):
        model = _model(family, policy)
        jaxpr = jax.make_jaxpr(jax.grad(_loss(model)))(_params(model))
        assert _count(jaxpr.jaxpr) == CALLS[policy]

    @pytest.mark.parametrize("policy", ["dots", "nothing"])
    def test_the_eva_mixer_follows_the_same_policies(self, policy):
        model = _model("llama-eva", policy)
        jaxpr = jax.make_jaxpr(jax.grad(_loss(model)))(_params(model))
        assert _count(jaxpr.jaxpr) == CALLS[policy]

    @pytest.mark.parametrize("policy", ["dots", "dots_lite"])
    def test_unscanned_layers_each_run_it_once(self, policy):
        model = _model("gpt", policy, scan_layers=False)
        jaxpr = jax.make_jaxpr(jax.grad(_loss(model)))(_params(model))
        assert _count(jaxpr.jaxpr) == 2 * 3

    @pytest.mark.parametrize("impl, names", [
        ("pallas", (*RESIDUAL_NAMES, "ffn_act")),
        ("xla", ("attn_out", "ffn_act")),
    ])
    def test_dots_lite_saves_the_kernels_names_where_the_kernel_runs(
        self, monkeypatch, impl, names
    ):
        """Without the kernel nothing carries its names: the policy
        saves ``attn_out`` as it always did."""
        asked = []
        monkeypatch.setattr(
            jax.checkpoint_policies, "save_only_these_names",
            lambda *names: asked.append(names),
        )
        stack.remat_policy(_model("gpt", "dots_lite", attn_impl=impl).cfg)
        assert asked == [names]

    def test_nothing_is_jaxs_nothing(self):
        policy = stack.remat_policy(_model("llama", "nothing").cfg)
        assert policy is jax.checkpoint_policies.nothing_saveable

    @pytest.mark.parametrize("policy", ["dots", "dots_lite", "nothing"])
    def test_through_shard_map_on_a_mesh(self, policy):
        """``data=2, fsdp=2``: the kernel runs under ``shard_map``, and
        the names inside its body reach the block's policy."""
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dlrover_tpu.accel import ParallelSpec
        from dlrover_tpu.accel.accelerate import make_train_step
        from dlrover_tpu.accel.mesh import create_mesh
        from dlrover_tpu.accel.sharding import state_shardings, unbox

        spec = ParallelSpec(data=2, fsdp=2)
        mesh = create_mesh(spec.axes(), devices=jax.devices()[:4])
        rules = spec.rules(vocab_size=40)
        model, opt = _model("gpt", policy), optax.sgd(1e-3)
        tokens = jnp.tile(TOKENS, (2, 1))

        def init_fn(rng):
            params = model.init(rng, tokens)["params"]
            return {"params": params, "opt": opt.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        def token_loss(module, params, batch):
            return gpt.loss_fn(module.apply({"params": params}, batch), batch)

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        shardings = state_shardings(mesh, abstract, rules)
        batch_sharding = NamedSharding(mesh, P(dict(rules)["batch"], None))
        step = make_train_step(
            model, opt, token_loss, mesh, rules, shardings, batch_sharding
        )
        jaxpr = jax.make_jaxpr(step)(unbox(abstract), tokens)
        assert _count(jaxpr.jaxpr, "shard_map") >= 3
        assert _count(jaxpr.jaxpr) == CALLS[policy]


class TestTheSameMathematics:
    @pytest.mark.parametrize("family", list(FAMILIES))
    def test_dots_and_dots_lite_give_the_bits_of_nothing(self, family):
        want = None
        for policy in ("nothing", "dots", "dots_lite"):
            model = _model(family, policy)
            got = jax.jit(jax.value_and_grad(_loss(model)))(_params(model))
            want = want or _bits(got)
            assert _bits(got) == want, policy
        assert any(np.frombuffer(b, np.float32).any() for b in want[1:])

    @pytest.mark.parametrize("mask", [
        AttentionMask(), eva_mask(96, 32, 8, 16), AttentionMask(window=32),
    ], ids=["causal", "eva", "window"])
    def test_a_call_under_a_policy_gives_the_bits_of_a_bare_call(self, mask):
        """The kernel alone, so no fusion differs between the programs:
        unremat'ed, remat'ed saving nothing, remat'ed saving the names."""
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (1, 96, 2, 16))
        k, v = (jax.random.normal(key, (1, 96 + mask.summaries, 2, 16))
                for key in ks[1:])

        def call(q, k, v):
            out = flash_attention(q, k, v, mask=mask, block_q=16, block_k=16)
            return jnp.sum(out * jnp.cos(out))

        policies = jax.checkpoint_policies
        want = _bits(jax.grad(call, argnums=(0, 1, 2))(q, k, v))
        for policy in (policies.nothing_saveable,
                       policies.save_only_these_names(*RESIDUAL_NAMES)):
            remat = jax.checkpoint(call, policy=policy)
            assert _bits(jax.grad(remat, argnums=(0, 1, 2))(q, k, v)) == want

    def test_the_names_are_what_the_backward_kernels_read(self):
        """Saving the two names leaves nothing of the forward kernel to
        recompute: the remat'ed gradient holds the two backward kernels
        and one forward; saving one name alone reruns it."""
        q, k, v = (jax.random.normal(key, (1, 64, 2, 16))
                   for key in jax.random.split(jax.random.PRNGKey(4), 3))

        def call(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=16, block_k=16) ** 2)

        def calls(*names):
            policy = jax.checkpoint_policies.save_only_these_names(*names)
            return _count(jax.make_jaxpr(jax.grad(
                jax.checkpoint(call, policy=policy)))(q, k, v).jaxpr)

        assert calls(*RESIDUAL_NAMES) == 3
        assert [calls(name) for name in RESIDUAL_NAMES] == [4, 4]
        assert calls() == 4


class TestTheCounter:
    @pytest.mark.parametrize("family", list(FAMILIES))
    @pytest.mark.parametrize("policy, kind", [
        ("dots", "saved"), ("dots_lite", "saved"),
        ("nothing", "recomputed"), ("offload", "recomputed"),
    ])
    def test_a_rematted_block_says_what_it_keeps(
        self, tracer, family, policy, kind
    ):
        model = _model(family, policy)
        jax.make_jaxpr(jax.grad(_loss(model)))(_params(model))
        events = _residual_events(tracer)
        # Raised each time the block's call is traced, by the bytes of
        # one call: the first event is one call's, the rest multiples.
        assert events[0] == {f"kind={kind}": RESIDUAL_BYTES}
        assert all(set(e) == {f"kind={kind}"} for e in events)
        assert events[-1][f"kind={kind}"] == len(events) * RESIDUAL_BYTES

    @pytest.mark.parametrize("family", list(FAMILIES))
    def test_absent_outside_a_rematted_block(self, tracer, family):
        model = _model(family, None)
        jax.make_jaxpr(jax.grad(_loss(model)))(_params(model))
        assert _residual_events(tracer) == []
        assert any(e["name"] == "attn.pairs" for e in tracer.events)

    def test_absent_where_no_kernel_runs(self, tracer):
        model = _model("gpt", "dots", attn_impl="xla")
        jax.make_jaxpr(jax.grad(_loss(model)))(_params(model))
        assert _residual_events(tracer) == []

    def test_bytes_follow_the_activation_dtype(self, tracer):
        model = _model("gpt", "dots", dtype=jnp.bfloat16)
        jax.make_jaxpr(jax.grad(_loss(model)))(_params(model))
        assert _residual_events(tracer)[0] == {
            "kind=saved": B * S * H * (D * 2 + 4)}

    def test_it_is_in_the_table_of_spans(self):
        layer, thread, covers = tracing.SPANS["attn.residuals"]
        assert layer == "kernels"
        assert "kind=saved" in covers and "kind=recomputed" in covers


class TestWhatTheSearchCounts:
    def test_dots_saves_the_kernels_output_too(self):
        from dlrover_tpu.accel.search import (
            ModelProfile,
            _act_floats_per_token_layer,
        )

        def floats(**kw):
            return _act_floats_per_token_layer(ModelProfile(
                param_count=1, d_model=100, ff_dim=400, **kw))

        assert floats(remat=True, remat_policy="dots") == 6 * 100 + 400
        assert floats(remat=True, remat_policy="nothing") == 2 * 100
        assert floats(remat=False) == 10 * 100 + 2 * 400
