"""What the plain references share: float32 ``jax.numpy`` with
``default_matmul_precision("highest")`` (on a TPU a float32 matmul is
otherwise computed in bfloat16 passes), no kernels, no cache.

Memory is the only concession: a reference runs beside the training state
on the same chip. So layers after the first are read from the program's
own stacked parameters one at a time and cast to float32 there, each layer
is recomputed in the backward pass (``jax.checkpoint``), attention goes
through the keys in blocks of queries, and the output head in blocks of
rows. None of that changes a value.

A family's file gives ``per_sequence_loss(embed, layer0, rest, tokens,
config)``; gradients are taken with respect to the embedding and the
first block, which sit at the far end of the backward pass.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
# For toy sizes (the CPU rehearsal, the tests, tools/toy_on_chip.py) in
# place of a family's TOLERANCE, which is set from chip runs at the
# published widths and has to tell bfloat16 from int8 there. At a toy
# size a gradient is a sum over a hundred tokens, so bfloat16 rounding is
# a larger part of it: tests/benchmark reads 1 - cosine of 3e-5 to 8e-5
# and norm ratios within 0.3 % of 1. These bounds leave that six times
# the room and still refuse 8-bit weights, which read 4e-3 to 2e-2.
TOY_TOLERANCE = {
    "loss_abs": 0.01,
    "embed": {"cosine_min": 0.9995, "norm_ratio": [0.99, 1.01]},
    "layer0": {"cosine_min": 0.9995, "norm_ratio": [0.99, 1.01]},
}
QUERY_BLOCK = 512
HEAD_ROWS = 2048


def causal_attention(q, k, v, query_block: int = QUERY_BLOCK):
    """Softmax attention with a causal mask. ``[B, S, H, D]`` float32."""
    b, s, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, F32))
    cols = jnp.arange(s)

    def block(start, q_rows):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) * scale
        rows = start + jnp.arange(q_rows.shape[1])
        scores = jnp.where(rows[:, None] >= cols[None, :], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    if s <= query_block:
        return block(0, q)
    if s % query_block:
        raise ValueError(f"sequence {s} not a multiple of {query_block}")
    n = s // query_block
    out = jax.lax.map(
        jax.checkpoint(
            lambda i: block(
                i * query_block,
                jax.lax.dynamic_slice_in_dim(
                    q, i * query_block, query_block, 1
                ),
            )
        ),
        jnp.arange(n),
    )  # [n, B, query_block, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def sequence_nll(x, head, tokens, rows: int = HEAD_ROWS):
    """Mean next-token negative log-likelihood of each sequence.

    ``x`` ``[B, S, d]`` final hidden states, ``head`` ``[d, V]``,
    ``tokens`` ``[B, S]``; position t predicts token t + 1, the last
    position predicts nothing. Returns ``[B]``.
    """
    b, s, d = x.shape
    x, targets = x[:, :-1], tokens[:, 1:]

    def nll(xr, tr):
        logits = xr @ head
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, tr[..., None], -1)[..., 0]

    n = s - 1
    if n <= rows:
        return jnp.mean(nll(x, targets), axis=-1)
    full = n // rows * rows
    parts = jax.lax.map(
        jax.checkpoint(lambda xt: jnp.sum(nll(*xt), axis=-1)),
        (
            jnp.moveaxis(x[:, :full].reshape(b, -1, rows, d), 1, 0),
            jnp.moveaxis(targets[:, :full].reshape(b, -1, rows), 1, 0),
        ),
    )  # [chunks, B]
    tail = jnp.sum(nll(x[:, full:], targets[:, full:]), axis=-1)
    return (jnp.sum(parts, axis=0) + tail) / n


def run_layers(x, layer, layer0, stacked):
    """``layer(x, p)`` for the first block's float32 parameters, then for
    blocks 1.. read one at a time from the stacked parameters."""
    x = layer(x, layer0)
    depth = next(iter(stacked.values())).shape[0]

    # The float32 copy of a block is made inside the checkpoint, so the
    # backward pass keeps the block's input and not its parameters.
    @jax.checkpoint
    def block(x, i):
        return layer(x, {
            name: jax.lax.dynamic_index_in_dim(w, i, 0, False).astype(F32)
            for name, w in stacked.items()
        })

    x, _ = jax.lax.scan(
        lambda x, i: (block(x, i), None), x, jnp.arange(1, depth)
    )
    return x


def split(ref_params: dict):
    """(embedding, first block, the rest): the first two as float32 copies
    that gradients are taken with respect to."""
    embed = ref_params["embed"].astype(F32)
    layer0 = {k: w[0].astype(F32) for k, w in ref_params["layers"].items()}
    return embed, layer0, ref_params


def loss_and_grads(reference, ref_params: dict, tokens, config: dict):
    """(per-sequence loss ``[B]``, gradients of their mean with respect to
    ``{"embed", "layer0"}``) by the family's ``reference`` module."""
    embed, layer0, rest = split(ref_params)

    def mean_loss(embed, layer0):
        with jax.default_matmul_precision("highest"):
            per_seq = reference.per_sequence_loss(
                embed, layer0, rest, tokens, config
            )
        return jnp.mean(per_seq), per_seq

    (_, per_seq), (g_embed, g_layer0) = jax.value_and_grad(
        mean_loss, argnums=(0, 1), has_aux=True
    )(embed, layer0)
    return per_seq, {"embed": g_embed, "layer0": g_layer0}


def losses(reference, ref_params: dict, tokens, config: dict):
    """Per-sequence loss ``[B]``, forward only."""
    embed, layer0, rest = split(ref_params)
    with jax.default_matmul_precision("highest"):
        return reference.per_sequence_loss(
            embed, layer0, rest, tokens, config
        )
