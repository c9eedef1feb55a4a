"""GPT-2-class decoder, TPU-first.

The flagship model for benchmarks and examples — the workload class the
reference optimizes (GLM/GPT LLM pretraining with ATorch's TP/SP/FSDP
modules, ``atorch/atorch/modules/distributed_modules/transformer.py``). This
is NOT a port of those torch modules: every parallelism is expressed as
flax *logical axis* metadata on params and activation constraints, which
GSPMD turns into sharded matmuls + collectives for whatever mesh the
caller provides (see ``dlrover_tpu/accel/sharding.py`` for the rules).

TPU specifics:
- bf16 activations / fp32 params by default (MXU-native);
- layers stacked with ``nn.scan`` so compile time is O(1) in depth;
- optional per-layer remat (``jax.checkpoint``) to trade FLOPs for HBM;
- attention is a plain einsum softmax by default — the Pallas
  flash/ring-attention kernel from ``dlrover_tpu.ops`` plugs in via
  ``attn_impl``.

Logical axis names used: batch, seq, embed, heads, kv, mlp, vocab.
"""

import dataclasses
from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from dlrover_tpu.models.stack import (
    attention,
    loss_fn,
    moe_loss_fn,
    run_blocks,
    run_pipeline,
)

__all__ = ["GPTConfig", "GPT", "Block", "loss_fn", "moe_loss_fn"]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 0  # 0 -> 4 * d_model
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    # "nothing": recompute everything (min memory); "dots": save matmul
    # outputs (recompute only cheap elementwise — the usual best
    # throughput/memory point when activations almost fit).
    remat_policy: str = "nothing"
    scan_layers: bool = True
    attn_impl: str = "xla"  # "xla" | "pallas" | "ring" | "ulysses"
    attn_block_q: int = 512  # pallas kernel tile sizes
    attn_block_k: int = 512
    # No dropout knob by design: modern LLM pretraining runs without it
    # (the reference's TP randomizer.py exists to keep torch dropout
    # masks per-rank-correct; JAX's explicit threefry keys make that a
    # non-problem — add flax nn.Dropout + a "dropout" rng collection in
    # a fine-tune recipe if one needs it).
    # "bf16" | "int8": int8 runs the MLP contractions as AQT-style
    # dynamic-quantized int8 matmuls (numerics-parity tested; currently
    # ~0.93x on v5e via this XLA build, which does not engage the
    # double-rate int8 MXU mode — see ops/quantized.py for measurements).
    mlp_precision: str = "bf16"
    # MoE (0 = dense MLP). With num_experts > 0 every block's FFN becomes
    # an expert-parallel MoEMLP and __call__ returns (logits, aux_loss).
    num_experts: int = 0
    moe_top_k: int = 2
    # Pipeline parallelism (0 = off). With pipeline_stages > 1 the blocks
    # are split into equal stages run as a GPipe schedule
    # (dlrover_tpu.accel.pipeline); pair with ParallelSpec(pipe=stages).
    # pipeline_repeats > 1 selects the circular/interleaved schedule
    # (CircularPipeline): stages*repeats chunks, ~repeats x smaller
    # bubble; requires microbatches >= stages. MoE composes with both
    # (the aux loss rides the pipeline carry).
    pipeline_stages: int = 0
    pipeline_microbatches: int = 0  # 0 -> = pipeline_stages
    pipeline_repeats: int = 1

    def __post_init__(self):
        if self.pipeline_stages > 1:
            chunks = self.pipeline_stages * max(self.pipeline_repeats, 1)
            if self.num_layers % chunks:
                raise ValueError(
                    f"num_layers {self.num_layers} not divisible by "
                    f"pipeline_stages*repeats {chunks}"
                )

    @property
    def ff_dim(self) -> int:
        return self.d_ff or 4 * self.d_model

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    def flops_per_token(self) -> float:
        """Approx training FLOPs/token (6*N_active params + attention)."""
        n = self.param_count(active=True)
        attn = 12 * self.num_layers * self.d_model * self.max_seq_len
        return 6 * n + attn

    def param_count(self, active: bool = False) -> int:
        """Total params; ``active=True`` counts only the top-k experts a
        token actually visits (the MoE FLOPs basis)."""
        d, f, v, l = self.d_model, self.ff_dim, self.vocab_size, self.num_layers
        if self.num_experts > 0:
            n_ffn = self.moe_top_k if active else self.num_experts
            mlp = n_ffn * (2 * d * f + f + d) + d * self.num_experts
        else:
            mlp = 2 * d * f
        per_layer = 4 * d * d + mlp + 4 * d  # qkvo + ffn/moe + ln
        return v * d + self.max_seq_len * d + l * per_layer + d

    def vocab_param_count(self) -> int:
        """Params living outside the layer stack (embedding + position
        table; the LM head is *tied* to the embedding, GPT-2 style) —
        what the pipeline cost model must not count as per-tick
        resident weights."""
        return self.vocab_size * self.d_model + self.max_seq_len * self.d_model

    @staticmethod
    def tiny():
        return GPTConfig(vocab_size=256, max_seq_len=64, num_layers=2,
                         num_heads=2, d_model=32)

    @staticmethod
    def gpt2_xl():
        """GPT-2 1.5B — BASELINE.md's checkpoint/perf model class."""
        return GPTConfig(vocab_size=50257, max_seq_len=1024, num_layers=48,
                         num_heads=25, d_model=1600, remat=True)


def _dense(features, name, kernel_axes, cfg: GPTConfig,
           quant: bool = False):
    kernel_init = nn.with_logical_partitioning(
        nn.initializers.normal(0.02), kernel_axes
    )
    bias_init = nn.with_logical_partitioning(
        nn.initializers.zeros_init(), (kernel_axes[-1],)
    )
    if quant and cfg.mlp_precision == "int8":
        from dlrover_tpu.ops.quantized import Int8Dense

        return Int8Dense(
            features, use_bias=True, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=kernel_init,
            bias_init=bias_init, name=name,
        )
    return nn.Dense(
        features,
        use_bias=True,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        kernel_init=kernel_init,
        bias_init=bias_init,
        name=name,
    )


def _layernorm(name, cfg: GPTConfig):
    return nn.LayerNorm(
        epsilon=1e-5,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        scale_init=nn.with_logical_partitioning(
            nn.initializers.ones_init(), ("embed",)
        ),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), ("embed",)
        ),
        name=name,
    )


class Block(nn.Module):
    """Pre-LN transformer block with TP-ready logical axes."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        b, s, d = x.shape
        h, hd = cfg.num_heads, cfg.head_dim

        y = _layernorm("ln1", cfg)(x)
        qkv = _dense(3 * d, "qkv", ("embed", "heads"), cfg)(y)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, h, hd)
        k = k.reshape(b, s, h, hd)
        v = v.reshape(b, s, h, hd)
        q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "kv"))
        k = nn.with_logical_constraint(k, ("batch", "seq", "heads", "kv"))
        v = nn.with_logical_constraint(v, ("batch", "seq", "heads", "kv"))
        attn = attention(q, k, v, cfg).reshape(b, s, d)
        from jax.ad_checkpoint import checkpoint_name
        attn = checkpoint_name(attn, "attn_out")
        x = x + _dense(d, "proj", ("heads", "embed"), cfg)(attn)

        y = _layernorm("ln2", cfg)(x)
        if cfg.num_experts > 0:
            from dlrover_tpu.ops.moe import MoEMLP

            y, aux = MoEMLP(
                num_experts=cfg.num_experts,
                ff_dim=cfg.ff_dim,
                top_k=cfg.moe_top_k,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="moe",
            )(y)
            x = x + y
            x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
            return x, aux
        y = _dense(cfg.ff_dim, "up", ("embed", "mlp"), cfg, quant=True)(y)
        y = nn.gelu(y)
        y = checkpoint_name(y, "ffn_act")
        y = nn.with_logical_constraint(y, ("batch", "seq", "mlp"))
        x = x + _dense(d, "down", ("mlp", "embed"), cfg, quant=True)(y)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        return x, None


class GPT(nn.Module):
    """Decoder-only LM. ``__call__(tokens[B,S]) -> logits[B,S,V]``."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        b, s = tokens.shape
        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            name="wte",
        )
        pos_embed = self.param(
            "wpe",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.01), ("seq", "embed")
            ),
            (cfg.max_seq_len, cfg.d_model),
            cfg.param_dtype,
        )
        x = embed(tokens) + pos_embed[None, :s].astype(cfg.dtype)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))

        if cfg.pipeline_stages > 1:
            x, aux = run_pipeline(Block, cfg, x)
        else:
            x, aux = run_blocks(
                Block, cfg, x, cfg.num_layers,
                scanned_name="blocks", unrolled_prefix="block_",
            )

        x = _layernorm("ln_f", cfg)(x)
        # Tied output head: logits via the embedding table (GPT-2 style).
        logits = embed.attend(x)  # module dtype (bf16): full MXU rate
        logits = nn.with_logical_constraint(
            logits, ("batch", "seq", "vocab")
        )
        if cfg.num_experts > 0:
            return logits, aux
        return logits
