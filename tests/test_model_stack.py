"""The names of a model's parameter tree, and that stacking does not
change what it computes.

Written checkpoints and the benchmark's ``to_reference`` read the trees
by these names (GPT ``blocks`` / ``block_{i}``, Llama ``layers`` /
``layer_{i}``, a pipeline's stages ``blocks`` / ``block_{i}``), which
``models/stack.py`` takes as arguments. The path cases go through
``jax.eval_shape`` of ``init`` and compile nothing. Toy sizes."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import gpt, llama

LAYERS, S = 4, 16
TOKENS = jnp.arange(2 * S).reshape(2, S) % 40

# family -> (module, config, fields, the model's own leaves, a dense
# block's leaves, what a MoE block holds in place of its MLP)
MOE = {"router", "w_up", "b_up", "w_down", "b_down"}
FAMILIES = {
    "gpt": (
        gpt.GPT, gpt.GPTConfig, dict(num_heads=4, d_model=32),
        {"wte/embedding", "wpe", "ln_f/scale", "ln_f/bias"},
        {f"{m}/{leaf}" for m in ("ln1", "qkv", "proj", "ln2")
         for leaf in ("scale" if m.startswith("ln") else "kernel", "bias")},
        {"up/kernel", "up/bias", "down/kernel", "down/bias"},
        {f"moe/{leaf}" for leaf in MOE},
    ),
    "llama": (
        llama.Llama, llama.LlamaConfig,
        dict(num_heads=4, num_kv_heads=2, d_model=32, d_ff=64),
        {"embed/embedding", "final_norm/scale", "lm_head/kernel"},
        {"attn_norm/scale", "q_proj/kernel", "k_proj/kernel",
         "v_proj/kernel", "o_proj/kernel", "mlp_norm/scale"},
        {"gate_proj/kernel", "up_proj/kernel", "down_proj/kernel"},
        {f"moe/{leaf}" for leaf in MOE | {"w_gate"}},
    ),
}
# stacking -> (config fields, where the blocks' trees sit by family)
STACKS = {
    "scanned": (
        dict(scan_layers=True),
        {"gpt": ["blocks"], "llama": ["layers"]},
    ),
    "unrolled": (
        dict(scan_layers=False),
        {"gpt": [f"block_{i}" for i in range(LAYERS)],
         "llama": [f"layer_{i}" for i in range(LAYERS)]},
    ),
    "staged": (
        dict(pipeline_stages=2, pipeline_microbatches=2),
        dict.fromkeys(FAMILIES, ["pipeline/ticks/stages/stage/blocks"]),
    ),
    "staged-unrolled": (
        dict(pipeline_stages=2, pipeline_microbatches=2, scan_layers=False),
        dict.fromkeys(FAMILIES, [
            f"pipeline/ticks/stages/stage/block_{i}" for i in range(2)
        ]),
    ),
    "circular": (
        dict(pipeline_stages=2, pipeline_repeats=2,
             pipeline_microbatches=2),
        dict.fromkeys(FAMILIES, ["pipeline/bank/blocks"]),
    ),
}


def _model(family, moe, **fields):
    module, config, extra = FAMILIES[family][:3]
    return module(config(
        vocab_size=40, max_seq_len=S, num_layers=LAYERS,
        dtype=jnp.float32, num_experts=4 if moe else 0,
        **extra, **fields,
    ))


def _paths(tree):
    return {
        "/".join(str(k.key) for k in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


@pytest.mark.parametrize("moe", [False, True], ids=["dense", "moe"])
@pytest.mark.parametrize("stack", STACKS)
@pytest.mark.parametrize("family", FAMILIES)
def test_parameter_paths(family, stack, moe):
    fields, prefixes = STACKS[stack]
    top, attn, mlp, experts = FAMILIES[family][3:]
    model = _model(family, moe, **fields)
    tree = jax.eval_shape(
        lambda: nn.meta.unbox(
            model.init(jax.random.PRNGKey(0), TOKENS)["params"]
        )
    )
    block = attn | (experts if moe else mlp)
    assert _paths(tree) == top | {
        f"{prefix}/{leaf}" for prefix in prefixes[family] for leaf in block
    }


@pytest.mark.parametrize("moe", [False, True], ids=["dense", "moe"])
@pytest.mark.parametrize("family", FAMILIES)
def test_scanned_and_unrolled_give_the_same_loss(family, moe):
    """The scanned tree's layer i is the unrolled tree's ``block_i`` /
    ``layer_i``: the same weights give the same loss either way."""
    scanned = _model(family, moe, scan_layers=True)
    unrolled = _model(family, moe, scan_layers=False)
    params = nn.meta.unbox(
        scanned.init(jax.random.PRNGKey(0), TOKENS)["params"]
    )
    key, name = (
        ("blocks", "block_{}") if family == "gpt" else ("layers", "layer_{}")
    )
    split = {k: v for k, v in params.items() if k != key}
    for i in range(LAYERS):
        split[name.format(i)] = jax.tree_util.tree_map(
            lambda x: x[i], params[key]
        )

    def loss(model, p):
        out = model.apply({"params": p}, TOKENS)
        if moe:
            return gpt.moe_loss_fn(out, TOKENS)
        return gpt.loss_fn(out, TOKENS)

    np.testing.assert_allclose(
        loss(scanned, params), loss(unrolled, split), rtol=1e-6
    )
