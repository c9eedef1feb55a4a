"""What the work requires, computed from shapes, and the chip's peaks.

Conventions, stated once:

- A training step requires, per token, 6 FLOPs for every weight that sits
  in a matrix multiplication (forward 2, backward 4): the projections, the
  MLP and the output head. The embedding lookup is a gather and counts 0.
- Causal attention is counted once: a query at position t needs t + 1
  keys, (S + 1) / 2 on average, not S. Forward is QK^T and PV, 4 FLOPs per
  query-key pair per head dimension; backward is twice the forward.
- Recomputation (remat, the flash kernel's second pass over QK^T beyond
  the one the backward needs) is not required work and counts 0.
- A kernel's bytes are its operands read once and its results written once.

Pure Python: the parent process uses this without JAX.
"""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str, path: str = "") -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(path or os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device kind {device_kind!r} is not in peaks.json "
            f"(have {sorted(k for k in table if not k.startswith('_'))})"
        )
    return table[device_kind]


def attention_flops_per_token(sizes: dict, seq: int) -> float:
    """Forward + backward FLOPs of causal attention per token."""
    width = sizes["heads"] * sizes["head_dim"]
    return 3 * 2 * width * (seq + 1) * sizes["layers"]


def train_flops_per_token(sizes: dict, seq: int) -> float:
    """Required forward + backward FLOPs per token of a training step."""
    return 6 * sizes["matmul_params"] + attention_flops_per_token(sizes, seq)


def flash_attention_cost(kind: str, batch: int, heads: int, seq: int,
                         head_dim: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) one call of the flash-attention kernel ``kind``
    requires on ``[batch, seq, heads, head_dim]`` with a causal mask.

    ``fwd``: S = QK^T, O = PV. ``dq``: S again (the backward needs P),
    dP = dO V^T, dQ = dS K. ``dkv``: dV = P^T dO, dK = dS^T Q, and as a
    kernel of its own it needs S and dP again; those two are counted in
    ``dq`` only, so ``dq`` + ``dkv`` is the 5 matmuls of one backward.
    """
    pairs = batch * heads * seq * (seq + 1) / 2
    tensor = batch * heads * seq * head_dim * itemsize
    row = batch * heads * seq * 4  # lse / delta, float32
    matmuls, reads, writes, rows = {
        "fwd": (2, 3, 1, 1),       # q k v -> o, lse
        "dq": (3, 4, 1, 2),        # q k v do, lse delta -> dq
        "dkv": (2, 4, 2, 2),       # q k v do, lse delta -> dk dv
    }[kind]
    flops = matmuls * 2 * pairs * head_dim
    return flops, (reads + writes) * tensor + rows * row


def adam8bit_cost(n_params: int, param_itemsize: int, grad_itemsize: int,
                  block: int = 256) -> tuple:
    """(FLOPs, bytes) of the fused 8-bit Adam update over ``n_params``:
    read the gradient, the parameter, two int8 moments and their float32
    block scales; write the parameter, the moments and the scales."""
    scales = 2 * 4 * n_params / block
    bytes_ = n_params * (grad_itemsize + 2 * param_itemsize + 4) + 2 * scales
    # dequantize, two moment updates, rsqrt, update, two requantizations
    return 24.0 * n_params, bytes_


def roofline_seconds(flops: float, bytes_: float, peaks: dict) -> tuple:
    """(least seconds the chip could take, which peak bounds it)."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
