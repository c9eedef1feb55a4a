"""GPT-2 family: a config file (the keys of the model's published
``config.json``) onto the program's ``GPTConfig``."""


def sizes(config: dict) -> dict:
    """Counts from the config file alone (no JAX)."""
    d, layers, vocab = config["n_embd"], config["n_layer"], config["vocab_size"]
    ff = config.get("n_inner") or 4 * d
    # qkv, proj, up, down; their biases; two LayerNorms
    layer_matmul = d * 3 * d + d * d + 2 * d * ff
    layer = layer_matmul + (3 * d + d + ff + d) + 4 * d
    return {
        "layers": layers, "d_model": d, "heads": config["n_head"],
        "head_dim": d // config["n_head"], "kv_heads": config["n_head"],
        "vocab": vocab, "positions": config["n_positions"],
        "params_per_layer": layer,
        # tied head: the embedding is counted once as a parameter ...
        "params": vocab * d + config["n_positions"] * d + layers * layer
        + 2 * d,
        # ... and once as the output projection's weight
        "matmul_params": layers * layer_matmul + vocab * d,
    }


def build(config: dict, job: dict) -> dict:
    """The program's module and loss for this config under this job."""
    import jax.numpy as jnp

    from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn

    if config["activation_function"] != "gelu_new" or not config.get(
        "tie_word_embeddings", True
    ):
        raise ValueError("models/gpt.py is tanh-GELU with a tied head")
    attention = job.get("attention", {})
    cfg = GPTConfig(
        vocab_size=config["vocab_size"], max_seq_len=config["n_positions"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        d_model=config["n_embd"], d_ff=config.get("n_inner") or 0,
        param_dtype=jnp.dtype(job["param_dtype"]),
        remat=bool(job.get("remat")), remat_policy=job.get("remat") or "nothing",
        attn_impl=attention.get("impl", "xla"),
        attn_block_q=attention.get("block_q", 512),
        attn_block_k=attention.get("block_k", 512),
        mlp_precision=job.get("mlp_precision", "bf16"),
    )

    def token_loss(module, params, batch):
        return loss_fn(module.apply({"params": params}, batch), batch)

    return {"module": GPT(cfg), "loss": token_loss, "cfg": cfg}


def to_reference(params) -> dict:
    """The program's parameter tree (or a gradient of its shape) under the
    names ``reference/gpt2.py`` uses. Layers stay stacked on axis 0."""
    b = params["blocks"]
    return {
        "embed": params["wte"]["embedding"],
        "pos": params["wpe"],
        "layers": {
            "ln1_scale": b["ln1"]["scale"], "ln1_bias": b["ln1"]["bias"],
            "w_qkv": b["qkv"]["kernel"], "b_qkv": b["qkv"]["bias"],
            "w_proj": b["proj"]["kernel"], "b_proj": b["proj"]["bias"],
            "ln2_scale": b["ln2"]["scale"], "ln2_bias": b["ln2"]["bias"],
            "w_up": b["up"]["kernel"], "b_up": b["up"]["bias"],
            "w_down": b["down"]["kernel"], "b_down": b["down"]["bias"],
        },
        "final": {
            "scale": params["ln_f"]["scale"], "bias": params["ln_f"]["bias"],
        },
    }
