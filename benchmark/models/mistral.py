"""Mistral family (pre-norm decoder: RMSNorm, RoPE, GQA, SwiGLU, untied
head, no biases): a config file (the keys of the model's published
``config.json``) onto the program's ``LlamaConfig``. The program has no
Mistral code of its own; ``models/llama.py`` computes this architecture."""


def sizes(config: dict) -> dict:
    """Counts from the config file alone (no JAX)."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    head_dim = config.get("head_dim") or d // heads
    ff, vocab = config["intermediate_size"], config["vocab_size"]
    layer_matmul = (
        d * heads * head_dim + 2 * d * kv * head_dim + heads * head_dim * d
        + 3 * d * ff
    )
    layer = layer_matmul + 2 * d
    return {
        "layers": layers, "d_model": d, "heads": heads, "head_dim": head_dim,
        "kv_heads": kv, "vocab": vocab,
        "positions": config["max_position_embeddings"],
        "params_per_layer": layer,
        "params": 2 * vocab * d + layers * layer + d,
        "matmul_params": layers * layer_matmul + vocab * d,
    }


def build(config: dict, job: dict) -> dict:
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import Llama, LlamaConfig, loss_fn

    d, heads = config["hidden_size"], config["num_attention_heads"]
    if (
        config["hidden_act"] != "silu" or config.get("tie_word_embeddings")
        or config.get("sliding_window")
        or config.get("head_dim", d // heads) != d // heads
        or config["rms_norm_eps"] != 1e-5
    ):
        raise ValueError(
            "models/llama.py is SiLU-gated, untied, full attention, head "
            "dimension hidden/heads, RMSNorm eps 1e-5"
        )
    attention = job.get("attention", {})
    cfg = LlamaConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=config["num_hidden_layers"], num_heads=heads,
        num_kv_heads=config["num_key_value_heads"], d_model=d,
        d_ff=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        param_dtype=jnp.dtype(job["param_dtype"]),
        remat=bool(job.get("remat")), remat_policy=job.get("remat") or "nothing",
        attn_impl=attention.get("impl", "xla"),
        attn_block_q=attention.get("block_q", 512),
        attn_block_k=attention.get("block_k", 512),
        mlp_precision=job.get("mlp_precision", "bf16"),
    )

    def token_loss(module, params, batch):
        return loss_fn(module.apply({"params": params}, batch), batch)

    return {"module": Llama(cfg), "loss": token_loss, "cfg": cfg}


def to_reference(params) -> dict:
    """The program's parameter tree (or a gradient of its shape) under the
    names ``reference/mistral.py`` uses. Layers stay stacked on axis 0."""
    b = params["layers"]
    return {
        "embed": params["embed"]["embedding"],
        "layers": {
            "attn_norm": b["attn_norm"]["scale"],
            "w_q": b["q_proj"]["kernel"], "w_k": b["k_proj"]["kernel"],
            "w_v": b["v_proj"]["kernel"], "w_o": b["o_proj"]["kernel"],
            "mlp_norm": b["mlp_norm"]["scale"],
            "w_gate": b["gate_proj"]["kernel"],
            "w_up": b["up_proj"]["kernel"],
            "w_down": b["down_proj"]["kernel"],
        },
        "final": {"scale": params["final_norm"]["scale"]},
        "head": params["lm_head"]["kernel"],
    }
