"""LLaMA-family decoder (RoPE + RMSNorm + SwiGLU + GQA), TPU-first.

Second flagship family — the reference's other headline workload class
(ATorch's GLM/LLaMA recipes drive the same Megatron-style TP modules,
``atorch/atorch/modules/distributed_modules/transformer.py``; HF LLaMA
is its standard demo model). Same design as :mod:`.gpt`: every
parallelism is logical-axis metadata + GSPMD, layers stack under
``nn.scan``, and the attention hot path plugs the Pallas flash / ring
kernels via ``attn_impl``.

Family-defining pieces, implemented TPU-first:
- RoPE applied to q/k at fp32 (precision of the rotation matters more
  than its FLOPs; XLA fuses it into the projection);
- RMSNorm (no mean subtraction, fp32 accumulation);
- SwiGLU MLP (gate/up/down, ``mlp`` axis for TP);
- grouped-query attention: ``num_kv_heads <= num_heads`` with kv heads
  repeated to query heads before the kernel (static-shape repeat — the
  MXU sees full-width matmuls; HBM holds only the small kv projection).

The block's mixer is chosen by the configuration: ``mixer="full"`` is
causal softmax attention over every earlier key; ``mixer="eva"`` is
window-local causal attention joined in one softmax with learned chunk
summaries of the earlier windows (``ops/eva.py``). A stack may mix layer
kinds: under the full mixer each layer's attention is one of ATTN_KINDS
(``attn_kinds``), and after ``dense_layers`` leading layers the FFN may be
one chip's share of a layer of routed experts (``experts``,
``ops/moe.py``). The variants an architecture may also ask for — RMSNorm
with a unit offset, a float32 residual stream, several next-token heads
with a float32 output, a head width of its own, normed q and k, a gated
attention output, sandwich norms, a scaled embedding — are fields whose
defaults leave the plain LLaMA program as it was.
"""

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.models.stack import (
    attention,
    count_residuals,
    counted_loss_fn,
    loss_fn,
    moe_loss_fn,
    run_blocks,
    run_pipeline,
)
from dlrover_tpu.ops.moe import HeldExperts

__all__ = ["LlamaConfig", "Llama", "loss_fn", "moe_loss_fn",
           "multibyte_loss_fn", "counted_loss_fn"]

#: What an entry of ``LlamaConfig.attn_kinds`` may be: causal attention
#: over every earlier key with RoPE (the plain LLaMA layer), RoPE and a
#: window of ``attn_window`` keys that slides with the query, or causal
#: attention with no position encoding.
ATTN_KINDS = ("full", "sliding", "nope")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 2048
    num_layers: int = 16
    num_heads: int = 16
    num_kv_heads: int = 0  # 0 -> = num_heads (MHA); < heads = GQA
    d_model: int = 1024
    d_ff: int = 0  # 0 -> the LLaMA 8/3 * d_model rounded to 128
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "nothing"
    scan_layers: bool = True
    attn_impl: str = "xla"  # "xla" | "pallas" | "ring" | "ulysses"
    attn_block_q: int = 512
    attn_block_k: int = 512
    # MoE (0 = dense SwiGLU). With num_experts > 0 every block's FFN
    # becomes a Mixtral-style expert-parallel SwiGLU MoE and __call__
    # returns (logits, aux_loss); pair with ParallelSpec(expert=K).
    num_experts: int = 0
    moe_top_k: int = 2
    # "bf16" | "int8": AQT-style dynamic-quantized int8 MLP matmuls
    # (ops/quantized.py, same contract + measured caveats as
    # GPTConfig.mlp_precision).
    mlp_precision: str = "bf16"
    # Pipeline parallelism (0 = off): same contract as GPTConfig —
    # stages run as GPipe (repeats == 1) or the circular/interleaved
    # schedule (repeats > 1); pair with ParallelSpec(pipe=stages).
    pipeline_stages: int = 0
    pipeline_microbatches: int = 0  # 0 -> = pipeline_stages
    pipeline_repeats: int = 1
    # The block's mixer. "full": causal attention over all earlier keys.
    # "eva": causal attention inside aligned windows of ``attn_window``
    # positions, in one softmax with one learned summary per
    # ``attn_chunk`` positions of every earlier window (ops/eva.py);
    # sequences must be whole windows.
    mixer: str = "full"
    attn_window: int = 0
    attn_chunk: int = 0
    # RMSNorm as x * rsqrt(mean(x^2) + eps) * (1 + g), g stored (zeros
    # at init), instead of a stored scale (ones at init).
    norm_unit_offset: bool = False
    # Keep the residual stream in float32 between blocks (the branches
    # still compute in ``dtype``).
    fp32_residual: bool = False
    # Next-token heads: head m at position t predicts token t + 1 + m;
    # the output is [B, S, pred_heads * vocab] (see multibyte_loss_fn).
    pred_heads: int = 1
    fp32_logits: bool = False  # the head's matmul accumulates and gives f32
    # Standard deviation every matrix, the embedding and the summaries'
    # vectors are drawn with. Not a free choice of the caller's: where an
    # architecture publishes its own (EvaByte: 0.01275) the first steps
    # and the comparison with the plain reference read differently at
    # the default (PERF.md section 6, PR 30: first loss 6.54 against
    # 6.03, gradient 1 - cosine 1.7e-5 against 1.1e-5).
    init_std: float = 0.02
    # Layers of more than one kind in one stack (models/stack.run_blocks
    # unrolls them). ``attn_kinds``: one of ATTN_KINDS a layer, for the
    # "full" mixer (empty: every layer is "full"). ``experts``: one chip's
    # share of a layer of routed experts (ops/moe.HeldExperts) as the FFN
    # of every layer after the ``dense_layers`` leading ones, which keep
    # the SwiGLU of width ``d_ff``; ``__call__`` then returns (logits,
    # routing counters), see ``counted_loss_fn``.
    attn_kinds: tuple = ()
    experts: Optional[HeldExperts] = None
    dense_layers: int = 0
    # The width of one head where it is not d_model / num_heads
    # (0: it is); the projections are then num_heads * head_dim wide.
    attn_head_dim: int = 0
    # RMSNorm of q and of k over the head width, a learned scale each,
    # before the position encoding.
    qk_norm: bool = False
    # The attention output times sigmoid(x W_g), W_g as wide as q,
    # before the output projection.
    attn_gate: bool = False
    # A norm after each branch as well as before: h + N2(Attn(N1(h))).
    sandwich_norm: bool = False
    # The embedding's rows times sqrt(d_model).
    scale_embed: bool = False

    def __post_init__(self):
        if self.kv_heads > self.num_heads or self.num_heads % self.kv_heads:
            raise ValueError(
                f"num_kv_heads {self.kv_heads} must divide num_heads "
                f"{self.num_heads}"
            )
        if self.mixer not in ("full", "eva"):
            raise ValueError(f"unknown mixer {self.mixer!r}")
        if self.attn_kinds:
            if self.mixer != "full" or len(self.attn_kinds) != self.num_layers:
                raise ValueError(
                    f"attn_kinds names one kind for each of the "
                    f"{self.num_layers} layers of a full mixer"
                )
            unknown = set(self.attn_kinds) - set(ATTN_KINDS)
            if unknown or (
                "sliding" in self.attn_kinds and self.attn_window <= 0
            ):
                raise ValueError(
                    f"attention kinds are {ATTN_KINDS}, sliding with an "
                    f"attn_window; got {sorted(unknown) or self.attn_kinds}"
                )
        if self.experts is not None and (
            self.num_experts or self.pipeline_stages > 1
        ):
            raise ValueError(
                "held experts run neither beside num_experts nor in "
                "pipeline stages"
            )
        if self.mixer == "eva":
            if not (self.attn_chunk > 0 and self.attn_window > 0
                    and self.attn_window % self.attn_chunk == 0):
                raise ValueError(
                    f"the eva mixer needs a window ({self.attn_window}) "
                    f"that is a multiple of its chunk ({self.attn_chunk})"
                )
            if self.attn_impl not in ("xla", "pallas"):
                raise ValueError(
                    "the eva mixer runs under attn_impl xla or pallas, "
                    f"not {self.attn_impl!r}"
                )
        if self.pipeline_stages > 1:
            chunks = self.pipeline_stages * max(self.pipeline_repeats, 1)
            if self.num_layers % chunks:
                raise ValueError(
                    f"num_layers {self.num_layers} not divisible by "
                    f"pipeline_stages*repeats {chunks}"
                )

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def ff_dim(self) -> int:
        if self.d_ff:
            return self.d_ff
        raw = int(8 * self.d_model / 3)
        return (raw + 127) // 128 * 128

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.d_model // self.num_heads

    def attn_kind(self, layer: int) -> str:
        return self.attn_kinds[layer] if self.attn_kinds else "full"

    def routed(self, layer: int) -> bool:
        """Whether layer ``layer``'s FFN is the held experts."""
        return self.experts is not None and layer >= self.dense_layers

    @property
    def routed_layers(self) -> int:
        return sum(self.routed(i) for i in range(self.num_layers))

    def layer_kinds(self):
        """What ``run_blocks`` builds each layer's block with: ``None``
        for a stack of one kind built as it always was."""
        if not self.attn_kinds and self.experts is None:
            return None
        return [
            {"attn_kind": self.attn_kind(i), "routed": self.routed(i)}
            for i in range(self.num_layers)
        ]

    def param_count(self) -> int:
        d, f, l = self.d_model, self.ff_dim, self.num_layers
        q, kv = self.num_heads * self.head_dim, self.kv_heads * self.head_dim
        attn = 2 * d * q + 2 * d * kv
        norms = 2 * d
        if self.mixer == "eva":     # the summaries' two vectors a head
            attn += 2 * q
        if self.attn_gate:
            attn += d * q
        if self.qk_norm:
            norms += 2 * self.head_dim
        if self.sandwich_norm:
            norms += 2 * d
        routed = self.routed_layers
        ffn = (l - routed) * 3 * d * f
        if routed:
            ffn += routed * self.experts.param_count(d)
        return self.vocab_param_count() + l * (attn + norms) + ffn + d

    def active_param_count(self) -> float:
        """``param_count`` with, of the held experts, the share a token
        passes through under a uniform router."""
        if self.experts is None:
            return self.param_count()
        return self.param_count() - self.routed_layers * (
            self.experts.param_count(self.d_model)
            - self.experts.active_param_count(self.d_model)
        )

    def vocab_param_count(self) -> int:
        """Embedding + *untied* LM head (LLaMA convention), one head's
        width for each next-token head: the params outside the layer
        stack for the pipeline cost model."""
        return (1 + self.pred_heads) * self.vocab_size * self.d_model

    def attention_pairs(self, layer: int = 0) -> int:
        """Query-key pairs of one head of layer ``layer`` over
        ``max_seq_len`` positions, as ``flops_per_token`` counts them:
        the whole square for a stack of plain full layers (twice what
        its causal mask allows — the count the cost models were
        calibrated on, kept); the pairs the layer's own mask allows
        exactly for the eva mixer (local windows and the summaries seen)
        and for a stack with ``attn_kinds`` (causal or sliding)."""
        s = self.max_seq_len
        if self.mixer == "eva":
            from dlrover_tpu.ops.eva import eva_mask

            mask = eva_mask(s, self.attn_window, self.attn_chunk)
            return mask.pairs(s, s + mask.summaries)
        if not self.attn_kinds:
            return s * s
        return self.attention_mask(layer).pairs(s, s)

    def attention_mask(self, layer: int):
        """The mask of layer ``layer`` of the full mixer."""
        from dlrover_tpu.ops.attention import AttentionMask

        if self.attn_kind(layer) == "sliding":
            return AttentionMask(window=self.attn_window, sliding=True)
        return AttentionMask()

    def flops_per_token(self) -> float:
        """Approx training FLOPs/token: 6 a parameter a token passes
        through plus attention, 12 a pair and unit of the heads' joint
        width (QK^T and PV, forward and twice backward); which pairs,
        see ``attention_pairs``."""
        pairs_per_token = sum(
            self.attention_pairs(i) for i in range(self.num_layers)
        ) / self.max_seq_len
        attn = 12 * self.num_heads * self.head_dim * pairs_per_token
        return 6 * self.active_param_count() + attn

    @staticmethod
    def tiny():
        return LlamaConfig(vocab_size=256, max_seq_len=64, num_layers=2,
                           num_heads=4, num_kv_heads=2, d_model=32)


class _UnitOffsetRMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * (1 + scale)``: the stored parameter
    is the offset from one. Statistics in float32, result in ``dtype``."""

    cfg: "LlamaConfig"

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ("embed",)
            ),
            (x.shape[-1],), cfg.param_dtype,
        )
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + 1e-5
        )
        return (y * (1.0 + scale.astype(jnp.float32))).astype(cfg.dtype)


def _rms_norm(name: str, cfg: LlamaConfig, axes=("embed",)):
    if cfg.norm_unit_offset:
        return _UnitOffsetRMSNorm(cfg, name=name)
    return nn.RMSNorm(
        epsilon=1e-5,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        scale_init=nn.with_logical_partitioning(
            nn.initializers.ones_init(), axes
        ),
        name=name,
    )


def _dense(features, name, kernel_axes, cfg: LlamaConfig,
           quant: bool = False, **kwargs):
    kernel_init = nn.with_logical_partitioning(
        nn.initializers.normal(cfg.init_std), kernel_axes
    )
    if quant and cfg.mlp_precision == "int8":
        from dlrover_tpu.ops.quantized import Int8Dense

        return Int8Dense(
            features, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, kernel_init=kernel_init,
            name=name,
        )
    return nn.Dense(
        features,
        use_bias=False,  # LLaMA projections carry no biases
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        kernel_init=kernel_init,
        name=name,
        **kwargs,
    )


def rope(x, positions, theta: float = 10000.0):
    """Rotary embedding over [B, S, H, D] (D even), positions [S]."""
    d = x.shape[-1]
    freqs = 1.0 / (
        theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    )
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :]  # [1, S, 1, D/2]
    sin = jnp.sin(angles)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).reshape(x.shape)
    return out.astype(x.dtype)


class LlamaBlock(nn.Module):
    """One layer. ``attn_kind`` (one of ATTN_KINDS) and ``routed`` (the
    FFN is the held experts) are the layer's own where a stack mixes
    kinds; the defaults are the plain LLaMA layer."""

    cfg: LlamaConfig
    attn_kind: str = "full"
    routed: bool = False

    @nn.compact
    def __call__(self, x, _=None):
        cfg = self.cfg
        b, s, d = x.shape
        h, kvh, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim

        y = _rms_norm("attn_norm", cfg)(x)
        q = _dense(h * hd, "q_proj", ("embed", "heads"), cfg)(y)
        k = _dense(kvh * hd, "k_proj", ("embed", "heads"), cfg)(y)
        v = _dense(kvh * hd, "v_proj", ("embed", "heads"), cfg)(y)
        q = q.reshape(b, s, h, hd)
        k = k.reshape(b, s, kvh, hd)
        v = v.reshape(b, s, kvh, hd)
        if cfg.qk_norm:
            q = _rms_norm("q_norm", cfg, axes=("kv",))(q)
            k = _rms_norm("k_norm", cfg, axes=("kv",))(k)
        if self.attn_kind != "nope":
            positions = jnp.arange(s)
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        if kvh != h:
            # GQA: repeat kv heads up to query width (static shape; the
            # small kv projection is what saves HBM, not the repeat).
            k = jnp.repeat(k, h // kvh, axis=2)
            v = jnp.repeat(v, h // kvh, axis=2)
        q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "kv"))
        k = nn.with_logical_constraint(k, ("batch", "seq", "heads", "kv"))
        v = nn.with_logical_constraint(v, ("batch", "seq", "heads", "kv"))
        attn = self._mix(q, k, v).reshape(b, s, h * hd)
        from jax.ad_checkpoint import checkpoint_name
        attn = checkpoint_name(attn, "attn_out")
        if cfg.attn_gate:
            attn = attn * nn.sigmoid(
                _dense(h * hd, "attn_gate", ("embed", "heads"), cfg)(y)
            )
        attn = _dense(d, "o_proj", ("heads", "embed"), cfg)(attn)
        if cfg.sandwich_norm:
            attn = _rms_norm("attn_post_norm", cfg)(attn)
        x = x + attn

        y = _rms_norm("mlp_norm", cfg)(x)
        if cfg.num_experts > 0:
            from dlrover_tpu.ops.moe import MoEMLP

            y, aux = MoEMLP(
                num_experts=cfg.num_experts,
                ff_dim=cfg.ff_dim,
                top_k=cfg.moe_top_k,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                mlp_type="swiglu",
                name="moe",
            )(y)
            x = x + y
            x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
            return x, aux
        aux = None
        if self.routed:
            from dlrover_tpu.ops.moe import HeldExpertsMLP

            y, aux = HeldExpertsMLP(
                cfg.experts, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                init_std=cfg.init_std, name="experts",
            )(y)
        else:
            gate = _dense(cfg.ff_dim, "gate_proj", ("embed", "mlp"), cfg,
                          quant=True)(y)
            up = _dense(cfg.ff_dim, "up_proj", ("embed", "mlp"), cfg,
                        quant=True)(y)
            y = nn.silu(gate) * up
            y = checkpoint_name(y, "ffn_act")
            y = nn.with_logical_constraint(y, ("batch", "seq", "mlp"))
            y = _dense(d, "down_proj", ("mlp", "embed"), cfg, quant=True)(y)
        if cfg.sandwich_norm:
            y = _rms_norm("mlp_post_norm", cfg)(y)
        x = x + y
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        return x, aux

    def _mix(self, q, k, v):
        """The block's mixer over rotated q, k and v ``[B, S, H, D]``."""
        cfg = self.cfg
        if cfg.mixer != "eva":
            sliding = self.attn_kind == "sliding"
            return attention(
                q, k, v, cfg, window=cfg.attn_window if sliding else 0
            )
        from dlrover_tpu.ops.eva import eva_attention

        phi, mu = (
            self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.normal(cfg.init_std), ("heads", "kv")
                ),
                (cfg.num_heads, cfg.head_dim), cfg.param_dtype,
            )
            for name in ("summary_phi", "summary_mu")
        )
        count_residuals(cfg, q)
        return eva_attention(
            q, k, v, phi, mu, window=cfg.attn_window, chunk=cfg.attn_chunk,
            impl=cfg.attn_impl, block_q=cfg.attn_block_q,
            block_k=cfg.attn_block_k,
        )


class Llama(nn.Module):
    """Decoder-only LM. ``__call__(tokens[B,S]) -> logits[B,S,V]``; with
    ``num_experts`` ``(logits, aux loss)``, with ``experts`` ``(logits,
    routing counters)``."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        b, s = tokens.shape
        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(cfg.init_std), ("vocab", "embed")
            ),
            name="embed",
        )
        x = embed(tokens)
        if cfg.scale_embed:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
        if cfg.fp32_residual:
            # Each block adds its branches (in ``dtype``) onto this.
            x = x.astype(jnp.float32)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))

        if cfg.pipeline_stages > 1:
            x, aux = run_pipeline(LlamaBlock, cfg, x)
        else:
            x, aux = run_blocks(
                LlamaBlock, cfg, x, cfg.num_layers,
                scanned_name="layers", unrolled_prefix="layer_",
                kinds=cfg.layer_kinds(),
            )

        logits = self._head(x)
        if cfg.num_experts > 0 or cfg.experts is not None:
            return logits, aux
        return logits

    def _head(self, x):
        """Final norm and the untied LM head (LLaMA convention):
        ``[B, S, pred_heads * vocab]``, head m in columns ``[m * vocab,
        (m + 1) * vocab)``."""
        cfg = self.cfg
        x = _rms_norm("final_norm", cfg)(x)
        more = {}
        if cfg.fp32_logits:
            # Inputs in ``dtype`` at the MXU's full rate; the sums and
            # the result in float32.
            more["dot_general"] = functools.partial(
                jax.lax.dot_general, preferred_element_type=jnp.float32
            )
        logits = _dense(
            cfg.pred_heads * cfg.vocab_size, "lm_head", ("embed", "vocab"),
            cfg, **more,
        )(x)
        return nn.with_logical_constraint(logits, ("batch", "seq", "vocab"))


def multibyte_loss_fn(logits, tokens, pred_heads: int):
    """Cross entropy of ``pred_heads`` next-token heads: ``logits``
    ``[B, S, pred_heads * V]``, head m at position t predicts token ``t +
    1 + m``. The mean over the heads of each head's mean over the
    positions that have a target (``S - 1 - m`` of them). With one head
    this is ``loss_fn``."""
    b, s, _ = logits.shape
    logits = logits.reshape(b, s, pred_heads, -1).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)      # [B, S, M]
    ahead = np.arange(s)[:, None] + 1 + np.arange(pred_heads)[None, :]
    valid = ahead < s                                       # [S, M]
    targets = jnp.asarray(tokens)[:, np.minimum(ahead, s - 1)]  # [B, S, M]
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    per_head = jnp.sum(
        jnp.where(valid, lse - tgt, 0.0), axis=(0, 1)
    ) / (b * valid.sum(axis=0))
    return jnp.mean(per_head)
