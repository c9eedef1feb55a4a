"""EvaByte family (pre-norm decoder over bytes: RMSNorm with a unit
offset, RoPE, plain multi-head EVA attention — causal inside aligned
windows, joined in one softmax with learned chunk summaries of the earlier
windows —, SwiGLU, a float32 residual stream, eight next-byte heads with a
float32 output, no biases): a config file (the keys of the model's
published ``config.json``) onto the program's ``LlamaConfig``. The program
has no EvaByte decoder of its own: ``models/llama.py`` computes this
architecture with its ``eva`` mixer (``ops/eva.py``)."""


def sizes(config: dict) -> dict:
    """Counts from the config file alone (no JAX)."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    heads = config["num_attention_heads"]
    head_dim = config.get("head_dim") or d // heads
    ff, vocab = config["intermediate_size"], config["vocab_size"]
    pred = config["num_pred_heads"]
    layer_matmul = 4 * d * heads * head_dim + 3 * d * ff
    # two norms, and the summaries' two vectors a head (phi, mu)
    layer = layer_matmul + 2 * d + 2 * heads * head_dim
    return {
        "layers": layers, "layers_key": "num_hidden_layers", "d_model": d,
        "heads": heads, "head_dim": head_dim, "kv_heads": heads,
        "vocab": vocab, "pred_heads": pred,
        "window": config["window_size"], "chunk": config["chunk_size"],
        "positions": config["max_position_embeddings"],
        "params_per_layer": layer,
        "params": vocab * d + layers * layer + d + d * pred * vocab,
        "matmul_params": layers * layer_matmul + d * pred * vocab,
    }


def build(config: dict, job: dict) -> dict:
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import (
        Llama,
        LlamaConfig,
        multibyte_loss_fn,
    )

    d, heads = config["hidden_size"], config["num_attention_heads"]
    if (
        config["attention_class"] != "eva" or config["hidden_act"] != "silu"
        or config.get("attention_bias") or config.get("tie_word_embeddings")
        or config["num_key_value_heads"] != heads
        or config.get("head_dim", d // heads) != d // heads
        or config["rms_norm_eps"] != 1e-5 or config.get("rope_scaling")
        or config.get("num_chunks") is not None
        or not (config["norm_add_unit_offset"] and config["fp32_skip_add"]
                and config["fp32_logits"] and config["mixedp_attn"])
    ):
        raise ValueError(
            "models/llama.py's eva mixer is plain multi-head, SiLU-gated, "
            "untied and without biases, head dimension hidden/heads, "
            "RMSNorm eps 1e-5 with a unit offset, unscaled RoPE, a float32 "
            "residual stream, float32 logits and a float32 softmax, one "
            "summary a chunk (num_chunks null)"
        )
    attention = job.get("attention", {})
    cfg = LlamaConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=config["max_position_embeddings"],
        num_layers=config["num_hidden_layers"], num_heads=heads,
        num_kv_heads=heads, d_model=d, d_ff=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        param_dtype=jnp.dtype(job["param_dtype"]),
        remat=bool(job.get("remat")), remat_policy=job.get("remat") or "nothing",
        attn_impl=attention.get("impl", "xla"),
        attn_block_q=attention.get("block_q", 512),
        attn_block_k=attention.get("block_k", 512),
        mlp_precision=job.get("mlp_precision", "bf16"),
        mixer="eva", attn_window=config["window_size"],
        attn_chunk=config["chunk_size"], norm_unit_offset=True,
        fp32_residual=True, fp32_logits=True,
        pred_heads=config["num_pred_heads"],
        init_std=float(config["init_std"]),
    )

    def multibyte_loss(module, params, batch):
        return multibyte_loss_fn(
            module.apply({"params": params}, batch), batch, cfg.pred_heads
        )

    return {"module": Llama(cfg), "loss": multibyte_loss, "cfg": cfg}


def to_reference(params) -> dict:
    """The program's parameter tree (or a gradient of its shape) under the
    names ``reference/evabyte.py`` uses. Layers stay stacked on axis 0; the
    norms' entries are the stored offsets (the scale is 1 + offset)."""
    b = params["layers"]
    return {
        "embed": params["embed"]["embedding"],
        "layers": {
            "attn_norm": b["attn_norm"]["scale"],
            "w_q": b["q_proj"]["kernel"], "w_k": b["k_proj"]["kernel"],
            "w_v": b["v_proj"]["kernel"], "w_o": b["o_proj"]["kernel"],
            "phi": b["summary_phi"], "mu": b["summary_mu"],
            "mlp_norm": b["mlp_norm"]["scale"],
            "w_gate": b["gate_proj"]["kernel"],
            "w_up": b["up_proj"]["kernel"],
            "w_down": b["down_proj"]["kernel"],
        },
        "final": {"scale": params["final_norm"]["scale"]},
        "head": params["lm_head"]["kernel"],
    }
