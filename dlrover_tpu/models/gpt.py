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
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


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
    # Layers per unrolled scan iteration: >1 cuts the XLA while-loop's
    # per-layer control overhead and widens the scheduler's window at
    # the cost of a proportionally larger program. Must divide
    # num_layers.
    scan_unroll: int = 1
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
    moe_capacity_factor: float = 1.25
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


def _attention(q, k, v, cfg: GPTConfig):
    """Causal attention. q,k,v: [B, S, H, D]."""
    if cfg.attn_impl == "pallas":
        from dlrover_tpu.ops.attention import flash_attention

        _count_residuals(cfg, q)
        return flash_attention(
            q, k, v, causal=True,
            block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
        )
    if cfg.attn_impl == "ring":
        from dlrover_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, causal=True, axis_name="seq")
    if cfg.attn_impl == "ulysses":
        from dlrover_tpu.ops.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, causal=True, axis_name="seq")
    scale = 1.0 / np.sqrt(cfg.head_dim)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = q.shape[1]
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))
    logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    probs = probs.astype(cfg.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


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
        attn = _attention(q, k, v, cfg).reshape(b, s, d)
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
                capacity_factor=cfg.moe_capacity_factor,
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



#: The policies under which a remat'ed block keeps what its flash
#: forward kernel wrote (``ops.attention.RESIDUAL_NAMES``).
_KEEPS_KERNEL_RESIDUALS = ("dots", "dots_lite")


def _remat_policy(cfg):
    """Shared by GPT and Llama (duck-typed on ``remat_policy`` and
    ``attn_impl``). What a remat'ed block keeps for its backward pass,
    and how often the flash kernel's forward (``attn_impl="pallas"``)
    then runs a layer a step:

    - "nothing": keep nothing, recompute everything (min HBM); the
      kernel's forward runs twice;
    - "dots": keep matmul outputs (usual throughput/memory sweet spot).
      The kernel is the block's largest pair of matmuls but a
      ``pallas_call`` and no ``dot_general``, so its two outputs, the
      attention output and the rows' log-sum-exp, are kept by name
      (``ops.attention.RESIDUAL_NAMES``): the forward runs once;
    - "dots_lite": keep ONLY the two expensive tensors per block — the
      attention output and the post-activation FFN tensor (named via
      ``checkpoint_name``) — and recompute the cheap qkv projections.
      Under the kernel the attention output is kept under the kernel's
      own names with its log-sum-exp (in place of ``attn_out``, the same
      tensor reshaped), so the forward runs once.
      ~55% of "dots"' activation bytes at a few percent recompute: the
      policy that buys batch 8 for the 1.5B single-chip preset
      (measured in bench.py's large section);
    - "offload": keep matmul outputs in *host* memory — activations
      leave HBM between fwd and bwd (parity: the reference's
      ``selective_offloading_checkpoint.py``); XLA streams them back
      over DMA during the backward pass. The kernel's outputs are not
      among them: the forward runs twice.
    """
    policies = jax.checkpoint_policies
    if cfg.remat_policy == "offload":
        return policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host"
        )
    if cfg.remat_policy not in _KEEPS_KERNEL_RESIDUALS:
        return policies.nothing_saveable
    from dlrover_tpu.ops.attention import RESIDUAL_NAMES

    if cfg.remat_policy == "dots":
        return policies.save_from_both_policies(
            policies.checkpoint_dots,
            policies.save_only_these_names(*RESIDUAL_NAMES),
        )
    attn = RESIDUAL_NAMES if cfg.attn_impl == "pallas" else ("attn_out",)
    return policies.save_only_these_names(*attn, "ffn_act")


def _count_residuals(cfg, q):
    """Where a block builds its attention over queries ``q``
    ``[B, S, H, D]``: if it is remat'ed and runs the flash kernel, raise
    the program's ``attn.residuals`` counter by the bytes of what the
    forward kernel writes for the backward ones, under whether the
    block's policy keeps them."""
    if cfg.remat and cfg.attn_impl == "pallas":
        from dlrover_tpu.ops.attention import count_residuals

        count_residuals(q, cfg.remat_policy in _KEEPS_KERNEL_RESIDUALS)


class _GPTStage(nn.Module):
    """One pipeline chunk: ``num_layers / (stages * repeats)`` blocks.
    Used as the ``make_stage`` body of ``accel.pipeline.Pipeline`` /
    ``CircularPipeline``. MoE chunks return ``(x, aux_mean)`` so the
    load-balance loss rides the pipeline carry."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        per_stage = cfg.num_layers // (
            cfg.pipeline_stages * max(cfg.pipeline_repeats, 1)
        )
        block = Block
        if cfg.remat:
            block = nn.remat(
                Block, prevent_cse=False,
                policy=_remat_policy(cfg),
            )
        if cfg.scan_layers:
            x, aux = nn.scan(
                block,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=per_stage,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="blocks")(x)
            aux_mean = jnp.mean(aux) if aux is not None else None
        else:
            auxes = []
            for i in range(per_stage):
                x, aux = block(cfg, name=f"block_{i}")(x)
                if aux is not None:
                    auxes.append(aux)
            aux_mean = jnp.mean(jnp.stack(auxes)) if auxes else None
        if cfg.num_experts > 0:
            return x, aux_mean
        return x


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
            from dlrover_tpu.accel.pipeline import (
                CircularPipeline,
                Pipeline,
            )

            if cfg.pipeline_repeats > 1:
                out = CircularPipeline(
                    make_stage=lambda: _GPTStage(cfg, name="stage"),
                    num_stages=cfg.pipeline_stages,
                    num_repeats=cfg.pipeline_repeats,
                    num_microbatches=cfg.pipeline_microbatches,
                    carry_axes=("batch", "seq", "embed"),
                    name="pipeline",
                )(x)
            else:
                out = Pipeline(
                    make_stage=lambda: _GPTStage(cfg, name="stage"),
                    num_stages=cfg.pipeline_stages,
                    num_microbatches=cfg.pipeline_microbatches,
                    carry_axes=("batch", "seq", "embed"),
                    has_aux=cfg.num_experts > 0,
                    name="pipeline",
                )(x)
            aux_total = None
            if cfg.num_experts > 0:
                x, aux_total = out
            else:
                x = out
            x = _layernorm("ln_f", cfg)(x)
            logits = embed.attend(x)  # module dtype (bf16): full MXU rate
            logits = nn.with_logical_constraint(
                logits, ("batch", "seq", "vocab")
            )
            if cfg.num_experts > 0:
                return logits, aux_total
            return logits

        block = Block
        if cfg.remat:
            block = nn.remat(
                Block, prevent_cse=False,
                policy=_remat_policy(cfg),
            )
        if cfg.scan_layers:
            x, aux = nn.scan(
                block,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
                unroll=max(cfg.scan_unroll, 1),
            )(cfg, name="blocks")(x)
            aux_total = jnp.mean(aux) if aux is not None else None
        else:
            auxes = []
            for i in range(cfg.num_layers):
                x, aux = block(cfg, name=f"block_{i}")(x)
                if aux is not None:
                    auxes.append(aux)
            aux_total = jnp.mean(jnp.stack(auxes)) if auxes else None

        x = _layernorm("ln_f", cfg)(x)
        # Tied output head: logits via the embedding table (GPT-2 style).
        logits = embed.attend(x)  # module dtype (bf16): full MXU rate
        logits = nn.with_logical_constraint(
            logits, ("batch", "seq", "vocab")
        )
        if cfg.num_experts > 0:
            return logits, aux_total
        return logits


def loss_fn(logits, tokens, ignore_first: bool = True):
    """Next-token cross entropy; logits[B,S,V], tokens[B,S].

    Computed as logsumexp - target_logit so no [B,S,V] f32 log-prob
    tensor is materialized (the logsumexp reduction streams over the
    vocab axis — at GPT-2 vocab size the full logp would be the largest
    activation in the model)."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def moe_loss_fn(out, tokens, aux_weight: float = 1e-2):
    """Loss for MoE models: ``out`` is ``(logits, aux)`` from a GPT with
    ``num_experts > 0``; adds the load-balance aux loss (Switch's 1e-2
    default weight)."""
    logits, aux = out
    return loss_fn(logits, tokens) + aux_weight * aux
