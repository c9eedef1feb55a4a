"""Trinity family (``afmoe``: sandwich-normed decoder, RMSNorm of q and k,
GQA whose output is gated by a sigmoid, sliding-window layers with RoPE and
full-attention layers with no position encoding by ``layer_types``, leading
dense SwiGLU layers and then sigmoid-routed experts beside a shared one,
an embedding scaled by sqrt(hidden), untied head, no biases): a config file
(the keys of the model's published ``config.json``) onto the program's
``LlamaConfig``. The program has no Trinity decoder of its own:
``models/llama.py`` computes this architecture from fields, the experts
through ``ops/moe.py``'s held-experts layer.

The file describes **one chip's share** of the layers it keeps:
``num_experts`` counts the routed experts this chip holds (experts
``0 .. num_experts - 1`` of each expert layer) while the router keeps the
published ``reduced_from.num_experts`` outputs; ``vocab_size`` counts the
rows of embedding and head it holds."""


def _routed(config: dict) -> int:
    """The router's outputs: every routed expert of a layer, on all chips."""
    return config.get("reduced_from", {}).get(
        "num_experts", config["num_experts"]
    )


def _layers(config: dict) -> tuple:
    """(kind of attention, whether the FFN is dense) of each layer kept."""
    n = config["num_hidden_layers"]
    return tuple(
        (kind, i < config["num_dense_layers"])
        for i, kind in enumerate(config["layer_types"][:n])
    )


def sizes(config: dict) -> dict:
    """Counts from the config file alone (no JAX)."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    kv, hd = config["num_key_value_heads"], config["head_dim"]
    vocab, held = config["vocab_size"], config["num_experts"]
    expert = 3 * d * config["moe_intermediate_size"]
    layers = _layers(config)
    dense = sum(is_dense for _, is_dense in layers)
    attention = 3 * d * heads * hd + 2 * d * kv * hd    # q, gate, o; k, v
    norms = 4 * d + 2 * hd
    dense_ffn = 3 * d * config["intermediate_size"]
    router = d * _routed(config)
    shared = config["num_shared_experts"] * expert
    # Of the held experts a token passes through the share a uniform
    # router sends it to: experts a token * held / routed of one expert.
    routed_share = config["num_experts_per_tok"] * held / _routed(config)
    dense_layer = attention + norms + dense_ffn
    expert_layer = attention + norms + router + shared + held * expert
    return {
        "layers": len(layers), "layers_key": "num_hidden_layers",
        "d_model": d, "heads": heads, "head_dim": hd, "kv_heads": kv,
        "vocab": vocab, "positions": config["max_position_embeddings"],
        "window": config["sliding_window"],
        "sliding_layers": sum(k == "sliding_attention" for k, _ in layers),
        "full_layers": sum(k == "full_attention" for k, _ in layers),
        "dense_layers": dense, "expert_layers": len(layers) - dense,
        "experts_held": held, "experts_routed": _routed(config),
        "experts_per_token": config["num_experts_per_tok"],
        "expert_ff": config["moe_intermediate_size"],
        "params_per_dense_layer": dense_layer,
        "params_per_expert_layer": expert_layer,
        "params": (
            2 * vocab * d + d + dense * dense_layer
            + (len(layers) - dense) * expert_layer
        ),
        "matmul_params": (
            vocab * d + len(layers) * attention + dense * dense_ffn
            + (len(layers) - dense) * (
                router + shared + routed_share * expert
            )
        ),
    }


def build(config: dict, job: dict) -> dict:
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import Llama, LlamaConfig
    from dlrover_tpu.ops.moe import HeldExperts

    layers = _layers(config)
    kinds = {"sliding_attention": "sliding", "full_attention": "nope"}
    if (
        config["model_type"] != "afmoe" or config["hidden_act"] != "silu"
        or config.get("tie_word_embeddings") or config.get("rope_scaling")
        or config["rms_norm_eps"] != 1e-5 or not config["mup_enabled"]
        or config["score_func"] != "sigmoid" or not config["route_norm"]
        or config["n_group"] != 1 or config["topk_group"] != 1
        or config["num_shared_experts"] != 1
        or any(kind not in kinds for kind, _ in layers)
        or len(layers) != config["num_hidden_layers"]
        or config["num_dense_layers"] != 1 or len(layers) < 2
    ):
        raise ValueError(
            "models/llama.py computes the afmoe layer: SiLU-gated, untied, "
            "unscaled RoPE on sliding layers and none on full ones, "
            "RMSNorm eps 1e-5, embedding times sqrt(hidden), sigmoid "
            "scores normalised over the chosen, one group, one shared "
            "expert, a layer type for every layer; and the comparison "
            "(to_reference) is of one leading dense layer and the first "
            "expert layer"
        )
    attention = job.get("attention", {})
    cfg = LlamaConfig(
        vocab_size=config["vocab_size"],
        max_seq_len=int(job.get("sequence", config["max_position_embeddings"])),
        num_layers=len(layers), num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        attn_head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        param_dtype=jnp.dtype(job["param_dtype"]),
        dtype=jnp.dtype(config.get("compute_dtype", "bfloat16")),
        remat=bool(job.get("remat")), remat_policy=job.get("remat") or "nothing",
        attn_impl=attention.get("impl", "xla"),
        attn_block_q=attention.get("block_q", 512),
        attn_block_k=attention.get("block_k", 512),
        mlp_precision=job.get("mlp_precision", "bf16"),
        attn_kinds=tuple(kinds[kind] for kind, _ in layers),
        attn_window=config["sliding_window"],
        dense_layers=config["num_dense_layers"],
        experts=HeldExperts(
            routed=_routed(config), held=config["num_experts"],
            per_token=config["num_experts_per_tok"],
            ff_dim=config["moe_intermediate_size"],
            pair_buffer=int(job.get("moe", {}).get("pair_buffer", 0)),
            route_scale=float(config["route_scale"]),
            shared_ff_dim=config["moe_intermediate_size"],
        ),
        qk_norm=True, attn_gate=True, sandwich_norm=True, scale_embed=True,
        scan_layers=False,
    )
    return {"module": Llama(cfg), "loss": DroplessLoss(), "cfg": cfg}


class DroplessLoss:
    """The cell's loss, which holds the program to its guarantee: the
    reference is dropless, so a step whose routed pairs did not fit the
    pair buffer (``moe.pairs{kind=overflowed}`` above 0) has no valid
    loss. It reports one that is not finite, which ``end_to_end.judge``
    counts as a failed step and a run that is not ``correct``; a step
    without overflow reports the mean next-token NLL untouched.

    Called, it gives the scalar (what ``worker.py``'s comparison
    differentiates); ``with_metrics`` gives ``(scalar, counters)``, the
    form ``accel.make_train_step`` carries into the step's metrics."""

    OVERFLOWED = "moe.pairs{kind=overflowed}"

    def with_metrics(self, module, params, batch):
        import jax.numpy as jnp

        from dlrover_tpu.models.llama import counted_loss_fn

        loss, counters = counted_loss_fn(
            module.apply({"params": params}, batch), batch
        )
        loss = jnp.where(counters[self.OVERFLOWED] > 0, jnp.nan, loss)
        return loss, counters

    def __call__(self, module, params, batch):
        return self.with_metrics(module, params, batch)[0]


def to_reference(params) -> dict:
    """The program's parameter tree (or a gradient of its shape) under the
    names ``reference/trinity.py`` uses. ``reference/common.py``
    differentiates the first entry of ``layers``: here that one entry is
    the leading dense layer (``d.*``) **and** the first expert layer
    (``e.*``) together, each leaf with a leading axis of one; the expert
    layers after it follow one by one under ``more`` (not stacked: a
    stack would copy them)."""
    import jax

    # Arrays, not tracers: wait for the program that makes them. The
    # leading axes below are copies (0.99 GB of them), and the device
    # allocates a copy when it is enqueued: beside the temporaries of a
    # gradient program still running there is no room for it.
    jax.block_until_ready(params)
    def attention(p):
        return {
            "attn_norm": p["attn_norm"]["scale"],
            "w_q": p["q_proj"]["kernel"], "w_k": p["k_proj"]["kernel"],
            "w_v": p["v_proj"]["kernel"], "w_g": p["attn_gate"]["kernel"],
            "w_o": p["o_proj"]["kernel"],
            "q_norm": p["q_norm"]["scale"], "k_norm": p["k_norm"]["scale"],
            "attn_post_norm": p["attn_post_norm"]["scale"],
            "mlp_norm": p["mlp_norm"]["scale"],
            "mlp_post_norm": p["mlp_post_norm"]["scale"],
        }

    def dense(p):
        return {
            **attention(p), "w_gate": p["gate_proj"]["kernel"],
            "w_up": p["up_proj"]["kernel"],
            "w_down": p["down_proj"]["kernel"],
        }

    def routed(p):
        e = p["experts"]
        return {
            **attention(p), "router": e["router"],
            "w_gate": e["w_gate"], "w_up": e["w_up"], "w_down": e["w_down"],
            "shared_gate": e["shared_gate"], "shared_up": e["shared_up"],
            "shared_down": e["shared_down"],
        }

    depth = sum(name.startswith("layer_") for name in params)
    first = {
        **{"d." + k: v[None] for k, v in dense(params["layer_0"]).items()},
        **{"e." + k: v[None] for k, v in routed(params["layer_1"]).items()},
    }
    return {
        "embed": params["embed"]["embedding"],
        "layers": first,
        "more": [routed(params[f"layer_{i}"]) for i in range(2, depth)],
        "final": {"scale": params["final_norm"]["scale"]},
        "head": params["lm_head"]["kernel"],
    }
