"""What a step of one chip's share of Trinity requires, computed from
shapes: the pairs each layer's mask allows, the FLOPs of a training step,
the FLOPs and bytes of its flash-attention calls by layer kind, and those
of one grouped matmul over a chunk of the pair buffer. The conventions are
``costs.py``'s (6 FLOPs a matmul parameter a token passes through,
attention counted once by the pairs the mask allows, recomputation 0, a
kernel's operands read once and its results written once). Pure Python.

A sliding layer's query i sees min(i + 1, W) keys, a full layer's i + 1:

    sliding = W (W + 1) / 2 + (S - W) W        (S >= W)
    full    = S (S + 1) / 2

Of the routed experts the required work is what a uniform router sends to
the experts held (``sizes()["matmul_params"]`` counts experts a token x
held / routed of one expert); the rows of the pair buffer beyond that are
padding, computed and not required.
"""

from benchmark import costs

KINDS = ("sliding", "full")


def pairs(seq: int, window: int) -> dict:
    """Query-key pairs of one head over ``seq`` positions, by layer kind."""
    w = min(window, seq)
    return {"sliding": w * (w + 1) // 2 + (seq - w) * w,
            "full": seq * (seq + 1) // 2}


def pairs_per_head(sizes: dict, seq: int) -> int:
    """The pairs of one head summed over the layers kept."""
    p = pairs(seq, sizes["window"])
    return (sizes["sliding_layers"] * p["sliding"]
            + sizes["full_layers"] * p["full"])


def attention_flops_per_token(sizes: dict, seq: int) -> float:
    """Forward + backward FLOPs of attention per token: QK^T and PV at 2
    FLOPs a pair and unit of head width, backward twice the forward."""
    qk, v = costs.head_widths(sizes)
    return 3 * 2 * (qk + v) * sizes["heads"] * pairs_per_head(sizes, seq) / seq


def train_flops_per_token(sizes: dict, seq: int) -> float:
    """Required forward + backward FLOPs per token of a training step."""
    return 6 * sizes["matmul_params"] + attention_flops_per_token(sizes, seq)


def flash_attention_cost(kind: str, layer: str, batch: int, sizes: dict,
                         seq: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) one call of the flash-attention kernel ``kind`` of
    a ``layer`` of KINDS requires: the pairs its mask allows, the five
    backward matmuls split between ``dq`` and ``dkv`` as
    ``costs.flash_attention_cost`` splits them; every tensor has ``seq``
    rows a head (the k/v heads arrive repeated to the query heads)."""
    qk, v = costs.head_widths(sizes)
    heads = batch * sizes["heads"]
    n_qk, n_v, wide_qk, wide_v, rows = {
        "fwd": (1, 1, 2, 2, 1),    # q k | v o | lse
        "dq": (2, 1, 3, 2, 2),     # q k dq | v do | lse delta
        "dkv": (1, 1, 3, 3, 2),    # q k dk | v do dv | lse delta
    }[kind]
    flops = 2 * heads * pairs(seq, sizes["window"])[layer] * (
        n_qk * qk + n_v * v
    )
    bytes_ = heads * seq * ((wide_qk * qk + wide_v * v) * itemsize + rows * 4)
    return flops, bytes_


def flash_attention_step_cost(kind: str, batch: int, sizes: dict,
                              seq: int) -> list:
    """(FLOPs, bytes) of each call of ``kind`` one pass over the layers
    makes: one a sliding layer, one a full layer."""
    return [
        flash_attention_cost(kind, layer, batch, sizes, seq)
        for layer in KINDS for _ in range(sizes[layer + "_layers"])
    ]


def grouped_matmul_cost(sizes: dict, rows: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) of one grouped matmul over ``rows`` rows of the pair
    buffer (the rows of one call), padding rows with the rest: ``[B, d] x [held, d, f]``
    (the SwiGLU's gate and up), ``[B, f] x [held, f, d]`` (down) and their
    backward products (the other operand's gradient: the same three shapes
    in another order) all contract or produce ``B x d x f``."""
    d, f, held = sizes["d_model"], sizes["expert_ff"], sizes["experts_held"]
    flops = 2 * rows * d * f
    bytes_ = (rows * (d + f) + held * d * f) * itemsize
    return flops, bytes_
