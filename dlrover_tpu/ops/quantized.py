"""Int8 quantized compute: weight-only serving + AQT-style training.

Capability parity with the reference's quantized-compute support
(``atorch/atorch/auto/opt_lib/amp_optimization.py:193`` fp8 via
TransformerEngine, ``atorch/atorch/ops/csrc/quantization/pt_binding.cpp``
CUDA kernels). v5e-class TPUs have no fp8 MXU but run **int8 at 2x the
bf16 MXU rate**, so the TPU-first analog of the reference's fp8
training is int8 quantized *training* matmuls, AQT-style:

- **Serving** (``quantize_params``/``dequantize_params``): kernels
  stored per-output-channel int8 + fp32 absmax scales; XLA fuses the
  dequant into consumers so int8 is what's HBM-resident.
- **Training** (``int8_dot`` / ``Int8Dense``): dynamic symmetric
  per-row (tokens) x per-column (features) quantization at each call;
  the contraction runs int8 x int8 -> int32 on the MXU and rescales to
  the activation dtype. The backward pass is straight-through: grads
  are computed in bf16 against the *unquantized* operands (the AQT
  recipe — quantization noise acts as a forward-only perturbation, so
  optimizer dynamics stay fp32-clean). Opt in per model via
  ``mlp_precision="int8"`` (GPTConfig/LlamaConfig) or
  ``auto_accelerate(precision="int8")``.

Measured (v5e single chip via this XLA build, 2026-07-30, interleaved
A/B/A): **no step-time win today** — 0.93x at 355M (224 vs 242 ms),
0.96x at 124M. A raw ``int8 x int8 -> int32`` dot microbenchmark runs
at the same rate as the bf16 dot (34.7 TOPS vs 36.2 TFLOP/s), i.e.
this XLA build does not engage the double-rate int8 MXU mode, and the
quantize chain + int32 output traffic add ~5%. The capability is kept
correct and opt-in: where the int8 MXU rate is exposed (other
XLA builds / TPU generations), the same code path is the 2x lever.
No cell of the benchmark runs it; ``benchmark/tools/precision_probe.py``
reads its gradients against a cell's reference.
"""

from typing import Any, Callable, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

__all__ = [
    "QuantizedWeight",
    "quantize_params",
    "dequantize_params",
    "quantized_nbytes",
    "int8_dot",
    "Int8Dense",
]

_MIN_QUANT_ELEMS = 1024  # tiny leaves (biases, norms) stay as-is


class QuantizedWeight(NamedTuple):
    q: jnp.ndarray        # int8, same shape as the original kernel
    scale: jnp.ndarray    # fp32 absmax per output channel (last dim)


def _quantizable(leaf) -> bool:
    return (
        hasattr(leaf, "ndim") and leaf.ndim >= 2
        and leaf.size >= _MIN_QUANT_ELEMS
        and jnp.issubdtype(leaf.dtype, jnp.floating)
    )


def quantize_params(params, min_elems: int = _MIN_QUANT_ELEMS):
    """Per-output-channel symmetric int8 quantization of every >=2D
    floating kernel; small leaves pass through unchanged."""

    def quant(leaf):
        if not _quantizable(leaf) or leaf.size < min_elems:
            return leaf
        x = leaf.astype(jnp.float32)
        scale = jnp.max(jnp.abs(x), axis=tuple(range(x.ndim - 1)))
        safe = jnp.where(scale == 0, 1.0, scale)
        q = jnp.clip(jnp.round(x / safe * 127.0), -127, 127).astype(
            jnp.int8
        )
        return QuantizedWeight(q=q, scale=scale.astype(jnp.float32))

    return jax.tree_util.tree_map(quant, params)


def dequantize_params(qparams, dtype=jnp.bfloat16):
    """bf16 view of a quantized pytree (fused into consumers under
    jit — the int8 storage stays resident, the view is transient)."""

    def dequant(leaf):
        if isinstance(leaf, QuantizedWeight):
            return (
                leaf.q.astype(jnp.float32) * (leaf.scale / 127.0)
            ).astype(dtype)
        return leaf

    return jax.tree_util.tree_map(
        dequant, qparams,
        is_leaf=lambda l: isinstance(l, QuantizedWeight),
    )


def quantized_nbytes(qparams) -> int:
    return sum(
        l.nbytes for l in jax.tree_util.tree_leaves(qparams)
        if hasattr(l, "nbytes")
    )


# --------------------------------------------------------------------------
# AQT-style int8 training matmul
# --------------------------------------------------------------------------

def _row_scale(x):
    """Symmetric absmax scale over the last (contraction) dim."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    return jnp.where(s == 0, 1.0, s).astype(jnp.float32)


def _col_scale(w):
    """Symmetric absmax scale over the first (contraction) dim -> [1, N].

    Reduces axis 0 directly instead of the old ``_row_scale(w.T).T``
    round-trip, so no transpose of the full kernel enters the graph."""
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    return jnp.where(s == 0, 1.0, s).astype(jnp.float32)


def _quant8(x, scale):
    return jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale * 127.0), -127, 127
    ).astype(jnp.int8)


@jax.custom_vjp
def int8_dot(x, w):
    """``x[..., K] @ w[K, N]`` with an int8 MXU contraction.

    Forward: dynamic symmetric quantization — per-row scales for ``x``
    (each token/position gets its own absmax over K), per-column scales
    for ``w`` — then ``int8 x int8 -> int32`` (``preferred_element_type``
    puts the accumulation on the MXU's int path at 2x bf16 rate) and a
    rank-1 rescale. Backward: straight-through in bf16 against the
    unquantized operands.
    """
    y, _ = _int8_dot_fwd(x, w)
    return y


def _int8_dot_fwd(x, w):
    sx = _row_scale(x)                      # [..., 1] per-row
    sw = _col_scale(w)                      # [1, N] per-column
    qx = _quant8(x, sx)
    qw = _quant8(w, sw)
    acc = jax.lax.dot_general(
        qx, qw,
        dimension_numbers=(((qx.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    y = acc.astype(jnp.float32) * (sx / 127.0) * (sw / 127.0)
    return y.astype(x.dtype), (x, w)


def _int8_dot_bwd(res, g):
    x, w = res
    gf = g.astype(x.dtype)
    dx = jax.lax.dot_general(
        gf, w,
        dimension_numbers=(((gf.ndim - 1,), (1,)), ((), ())),
    ).astype(x.dtype)
    x2 = x.reshape(-1, x.shape[-1])
    g2 = gf.reshape(-1, gf.shape[-1])
    dw = jax.lax.dot_general(
        x2, g2, dimension_numbers=(((0,), (0,)), ((), ())),
    ).astype(w.dtype)
    return dx, dw


int8_dot.defvjp(_int8_dot_fwd, _int8_dot_bwd)


class Int8Dense(nn.Module):
    """Drop-in for ``nn.Dense`` whose contraction runs ``int8_dot``.

    Same param structure (``kernel`` [+ ``bias``], same logical-axis
    boxing) as ``nn.Dense``, so sharding rules, the TP planner, FSDP and
    checkpoints all see an identical tree — precision is a pure compute
    swap, exactly like the reference flipping a linear to fp8 via
    TransformerEngine.
    """

    features: int
    use_bias: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    kernel_init: Optional[Callable] = None
    bias_init: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        kernel_init = self.kernel_init or nn.initializers.lecun_normal()
        kernel = self.param(
            "kernel", kernel_init, (x.shape[-1], self.features),
            self.param_dtype,
        )
        y = int8_dot(x.astype(self.dtype), kernel.astype(self.dtype))
        if self.use_bias:
            bias_init = self.bias_init or nn.initializers.zeros_init()
            bias = self.param(
                "bias", bias_init, (self.features,), self.param_dtype
            )
            y = y + bias.astype(self.dtype)
        return y
