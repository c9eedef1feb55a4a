"""What a step of the EVA mixer requires, computed from shapes: the pairs
its mask allows, the FLOPs of a training step, and the FLOPs and bytes of
its three flash-attention calls. The conventions are ``costs.py``'s
(6 FLOPs a matmul parameter, attention counted once by the pairs the mask
allows, recomputation 0, a kernel's operands read once and its results
written once); what differs is the pair count, which ``costs.py`` takes
as the full causal (S + 1) / 2 a query. Pure Python.

Queries of window w = i // W see their own window causally and one
summary per chunk of C positions of every earlier window:

    local  = (S / W) * W (W + 1) / 2
    remote = W * (W / C) * (0 + 1 + ... + (S / W - 1))
"""

from benchmark import costs


def pairs(seq: int, window: int, chunk: int) -> tuple:
    """(local, remote) query-key pairs of one head over ``seq`` positions."""
    if seq % window or window % chunk:
        raise ValueError(f"{seq} positions, window {window}, chunk {chunk}")
    windows = seq // window
    local = windows * window * (window + 1) // 2
    remote = window * (window // chunk) * (windows * (windows - 1) // 2)
    return local, remote


def summary_rows(seq: int, window: int, chunk: int) -> int:
    """Summary rows laid before the keys: one a chunk, none where one
    window is all there is."""
    return seq // chunk if seq > window else 0


def attention_flops_per_token(sizes: dict, seq: int) -> float:
    """Forward + backward FLOPs of the mixer per token: QK^T and PV at 2
    FLOPs a pair and unit of head width, backward twice the forward; and
    the summaries' three sums over each chunk (k.phi, sum a k, sum a v: 2
    FLOPs a position and unit of width each), likewise."""
    local, remote = pairs(seq, sizes["window"], sizes["chunk"])
    qk, v = costs.head_widths(sizes)
    per_head = 3 * 2 * (qk + v) * (local + remote) / seq
    if summary_rows(seq, sizes["window"], sizes["chunk"]):
        per_head += 3 * 2 * (2 * qk + v)
    return sizes["heads"] * sizes["layers"] * per_head


def train_flops_per_token(sizes: dict, seq: int) -> float:
    """Required forward + backward FLOPs per token of a training step."""
    return 6 * sizes["matmul_params"] + attention_flops_per_token(sizes, seq)


def flash_attention_cost(kind: str, batch: int, sizes: dict, seq: int,
                         itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) one call of the flash-attention kernel ``kind``
    requires under the EVA mask: the allowed pairs, with the five backward
    matmuls split between ``dq`` and ``dkv`` as
    ``costs.flash_attention_cost`` splits them; q, o, do, dq and the lse
    and delta rows have ``seq`` rows a head, k, v, dk, dv the summaries'
    rows more."""
    qk, v = costs.head_widths(sizes)
    heads = batch * sizes["heads"]
    keys = seq + summary_rows(seq, sizes["window"], sizes["chunk"])
    n_qk, n_v, q_side, k_side, rows = {
        #        matmuls | widths of seq-row tensors | of key-row tensors
        "fwd": (1, 1, qk + v, qk + v, 1),          # q o | k v | lse
        "dq": (2, 1, 2 * qk + v, qk + v, 2),       # q dq do | k v
        "dkv": (1, 1, qk + v, 2 * (qk + v), 2),    # q do | k v dk dv
    }[kind]
    flops = 2 * heads * sum(pairs(seq, sizes["window"], sizes["chunk"])) * (
        n_qk * qk + n_v * v
    )
    bytes_ = heads * (
        (seq * q_side + keys * k_side) * itemsize + seq * rows * 4
    )
    return flops, bytes_
