"""The program's models against the benchmark's plain references, at a
small size on the CPU: loss and gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, compare
from benchmark.reference import common

GPT2 = {
    "vocab_size": 512, "n_positions": 128, "n_embd": 64, "n_layer": 3,
    "n_head": 2, "layer_norm_epsilon": 1e-5,
    "activation_function": "gelu_new",
}
MISTRAL = {
    "vocab_size": 512, "max_position_embeddings": 4096, "hidden_size": 64,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 160,
    "rms_norm_eps": 1e-5, "rope_theta": 1e6, "hidden_act": "silu",
}
CONFIGS = {"gpt2": GPT2, "mistral": MISTRAL}

# Held to reference/common.py's TOY_TOLERANCE (its reason is written
# there); a chip run at the published widths is held to the family's own.
SMALL = common.TOY_TOLERANCE


def _job(param_dtype="bfloat16", impl="pallas", **more):
    return {"param_dtype": param_dtype, "remat": "dots",
            "attention": {"impl": impl, "block_q": 32, "block_k": 32},
            **more}


def _compare(family_name, job, batch=2, seq=64, lower=None):
    """The ``reference`` record a worker would write, and the system's
    loss, for random weights (biases and scales moved off their trivial
    initial values) and tokens from a seed."""
    import flax.linen as nn

    config = CONFIGS[family_name]
    family = cells.family_module("models", family_name)
    reference = cells.family_module("reference", family_name)
    built = family.build(config, job)
    module, loss = built["module"], built["loss"]
    tokens = np.random.default_rng(0).integers(
        0, config["vocab_size"], (batch, seq), dtype=np.int32
    )
    params = nn.meta.unbox(module.init(jax.random.PRNGKey(0), tokens)["params"])
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree_util.tree_unflatten(treedef, [
        x + 0.02 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)
    ])
    lower = lower or (lambda p: p)
    sys_loss, sys_grads = jax.jit(jax.value_and_grad(
        lambda p: loss(module, lower(p), tokens)
    ))(params)
    per_seq, ref_grads = jax.jit(lambda p, t: common.loss_and_grads(
        reference, family.to_reference(p), t, config
    ))(params, tokens)
    forward_only = jax.jit(lambda p, t: common.losses(
        reference, family.to_reference(p), t, config
    ))(params, tokens)
    np.testing.assert_allclose(per_seq, forward_only, rtol=1e-6)
    record = {
        "loss_ref_batch": float(per_seq.mean()),
        "agreement": compare.agreement(
            family.to_reference(sys_grads), ref_grads
        ),
        "tolerance": SMALL,
    }
    assert set(reference.TOLERANCE) == set(SMALL)
    return record, float(sys_loss)


@pytest.mark.parametrize("family", ["gpt2", "mistral"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("param_dtype", ["bfloat16", "float32"])
def test_system_agrees_with_the_reference(family, impl, param_dtype):
    record, sys_loss = _compare(family, _job(param_dtype, impl))
    assert compare.judge_reference(record, sys_loss) == []


@pytest.mark.parametrize("family", ["gpt2", "mistral"])
def test_a_lower_precision_than_stated_fails(family):
    """The job states bfloat16; weights rounded to 8 bits (float8 e4m3)
    are a lower precision and must not pass for it."""
    def float8(params):
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype), params
        )

    record, sys_loss = _compare(family, _job(), lower=float8)
    why = compare.judge_reference(record, sys_loss)
    assert any("cosine" in reason for reason in why), (why, record)


def test_a_wrong_loss_fails():
    record, sys_loss = _compare("gpt2", _job())
    assert compare.judge_reference(record, sys_loss + 0.02)
    assert compare.judge_reference(record, float("nan"))
    assert compare.judge_reference(record, None)


def test_long_sequences_go_through_blocks_and_agree():
    """1024 tokens: attention in two blocks of queries, and (rows lowered)
    the head in blocks of rows with a tail."""
    record, sys_loss = _compare(
        "mistral", _job("float32", "xla"), batch=1, seq=1024
    )
    assert compare.judge_reference(record, sys_loss) == []


def test_blocked_attention_equals_direct():
    q, k, v = (
        jax.random.normal(key, (2, 256, 3, 8))
        for key in jax.random.split(jax.random.PRNGKey(0), 3)
    )
    direct = common.causal_attention(q, k, v, query_block=256)
    blocked = common.causal_attention(q, k, v, query_block=64)
    np.testing.assert_allclose(direct, blocked, rtol=1e-5, atol=1e-6)
    # Causal: the first position sees itself only.
    np.testing.assert_allclose(direct[:, 0], v[:, 0], rtol=1e-6)
    with pytest.raises(ValueError):
        common.causal_attention(q, k, v, query_block=100)


def test_head_in_blocks_of_rows_equals_direct():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 38, 16))
    head = jax.random.normal(jax.random.PRNGKey(1), (16, 50))
    tokens = np.random.default_rng(0).integers(0, 50, (2, 38))
    direct = common.sequence_nll(x, head, tokens, rows=64)
    blocked = common.sequence_nll(x, head, tokens, rows=8)  # 4 blocks + 5
    np.testing.assert_allclose(direct, blocked, rtol=1e-5)
    # By hand for one sequence: mean of -log softmax at the next token.
    logp = jax.nn.log_softmax(x[0, :-1] @ head)
    want = -np.mean(logp[np.arange(37), tokens[0, 1:]])
    np.testing.assert_allclose(direct[0], want, rtol=1e-5)
