"""EvaByte, plainly (EvaByte 6.5B, ``config.json`` of EvaByte/EvaByte on
the Hugging Face hub; its mixer is EVA, Zheng et al. 2023, "Efficient
Attention via Control Variates", arXiv:2302.04542): pre-norm blocks over
bytes, RMSNorm whose stored parameter is the scale's offset from one,
rotary position embedding on q and k, plain multi-head attention that is
causal inside aligned windows and joined, in one softmax, with one learned
summary per chunk of every earlier window, SiLU-gated MLP, a float32
residual stream, a final norm and eight untied next-byte heads, no biases.

One head of width D, S positions, window W, chunk C, learned phi, mu in
R^D; q, k rotated at absolute positions:

    a_j   = softmax_j(k_j . phi)  over the chunk c = positions cC..cC+C-1
    kb_c  = sum_j a_j k_j + mu,   vb_c = sum_j a_j v_j
    L_i   = {j : j // W == i // W, j <= i}        local keys
    R_i   = {c : c < (i // W) * W / C}            summaries of earlier windows
    o_i   = (sum_L e^{q_i.k_j/sqrt D} v_j + sum_R e^{q_i.kb_c/sqrt D} vb_c) / Z_i
    Z_i   = the sum of the same exponentials

Head m at position t predicts byte t + 1 + m; a sequence's loss is the
mean over the heads of each head's mean cross-entropy over the positions
that have a target.

Everything is float32 under ``highest``; the masks are dense; queries go
through all S + S/C key rows in blocks, for memory only. Departures from
the published description, each also under ``assumed`` in
``configs/evabyte.json``: the source's ``fp32_ln: false`` (a bfloat16
norm) against float32 norm statistics here and in the program; RoPE over
adjacent pairs (2i, 2i+1) of the head dimension, as reference/mistral.py;
the heads weigh equally; no constant on ``k_j . phi``.
"""

import jax
import jax.numpy as jnp

from benchmark.reference import common

TOLERANCE = {
    # Each limit lies between two chip readings at the published widths
    # (my chip runs, PR 30; TPU v5 lite; PERF.md section 6): what the cell
    # read over six seeds (the gradient sample: 8192 bytes, four windows),
    # and what tools/precision_probe.py read with the int8 MLP, the
    # nearest precision below the stated bfloat16, which must fail.
    #
    # First-step loss of the 1 x 32768 batch against the reference's:
    # |gap| 5e-5 to 1.8e-4. The int8 MLP moves it as little (7e-5), so
    # the loss tells a wrong program, not a precision: the limit of the
    # accepted cells, 55 times the largest reading.
    "loss_abs": 0.01,
    # 1 - cosine: block 0 read 1.04e-5 to 1.30e-5, int8 1.43e-4; the
    # embedding 8.6e-6 to 1.16e-5, int8 1.40e-4. The limit 4e-5 is three
    # times the largest reading and 3.5 times under int8's.
    # Norm ratio: block 0 read 0.99991 to 1.00053, int8 0.99716; the
    # embedding 0.99999 to 1.00083, int8 0.99673. The limit, 2.5e-3 from
    # 1, is three times the largest reading; int8 lies 2.8e-3 and 3.3e-3
    # off (the cosine is the limit that tells it apart with room).
    "layer0": {"cosine_min": 0.99996, "norm_ratio": [0.9975, 1.0025]},
    "embed": {"cosine_min": 0.99996, "norm_ratio": [0.9975, 1.0025]},
}
QUERY_BLOCK = 256       # [heads, 256, S + S/C] float32 scores at a time


def _rms_norm(x, offset, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * (1.0 + offset)


def _rope(x, theta):
    """Rotate pairs (2i, 2i+1) of the head dimension by position * theta^(-2i/D)."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=common.F32) / d)
    angles = jnp.arange(s, dtype=common.F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [even * cos - odd * sin, even * sin + odd * cos], axis=-1
    ).reshape(x.shape)


def summaries(k, v, phi, mu, chunk):
    """``[B, S/C, H, D]`` summary keys and values of ``[B, S, H, D]``."""
    b, s, h, d = k.shape
    k = k.reshape(b, s // chunk, chunk, h, d)
    v = v.reshape(b, s // chunk, chunk, h, d)
    a = jax.nn.softmax(jnp.einsum("bcjhd,hd->bcjh", k, phi), axis=2)
    return (jnp.einsum("bcjh,bcjhd->bchd", a, k) + mu,
            jnp.einsum("bcjh,bcjhd->bchd", a, v))


def eva_attention(q, k, v, phi, mu, window, chunk,
                  query_block: int = QUERY_BLOCK):
    """One softmax over the local keys and the summaries of the earlier
    windows. ``[B, S, H, D]`` float32; phi, mu ``[H, D]``."""
    b, s, h, d = q.shape
    if s % window or window % chunk:
        raise ValueError(f"{s} positions are not whole windows of {window} "
                         f"of whole chunks of {chunk}")
    kb, vb = summaries(k, v, phi, mu, chunk)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, common.F32))
    cols, chunks = jnp.arange(s), jnp.arange(s // chunk)

    def block(start, q_rows):
        rows = start + jnp.arange(q_rows.shape[1])
        local = (cols[None, :] <= rows[:, None]) & (
            cols[None, :] // window == rows[:, None] // window
        )
        remote = chunks[None, :] * chunk // window < rows[:, None] // window
        scores = jnp.concatenate([
            jnp.where(remote, jnp.einsum("bqhd,bchd->bhqc", q_rows, kb)
                      * scale, -jnp.inf),
            jnp.where(local, jnp.einsum("bqhd,bkhd->bhqk", q_rows, k)
                      * scale, -jnp.inf),
        ], axis=-1)
        p = jax.nn.softmax(scores, axis=-1)
        return (jnp.einsum("bhqc,bchd->bqhd", p[..., :s // chunk], vb)
                + jnp.einsum("bhqk,bkhd->bqhd", p[..., s // chunk:], v))

    if s <= query_block:
        return block(0, q)
    if s % query_block:
        raise ValueError(f"sequence {s} not a multiple of {query_block}")
    out = jax.lax.map(
        jax.checkpoint(
            lambda i: block(
                i * query_block,
                jax.lax.dynamic_slice_in_dim(
                    q, i * query_block, query_block, 1
                ),
            )
        ),
        jnp.arange(s // query_block),
    )  # [n, B, query_block, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def multibyte_nll(x, head, tokens, pred_heads, rows: int = common.HEAD_ROWS):
    """Each sequence's loss ``[B]``: ``x`` ``[B, S, d]`` final hidden
    states, ``head`` ``[d, pred_heads * V]`` (head m in columns m V ..
    (m + 1) V - 1), position t of head m predicts token t + 1 + m."""
    b, s, d = x.shape
    head = head.reshape(d, pred_heads, -1)

    def head_nll(m):
        n = s - 1 - m
        logits = jnp.einsum("bnd,dv->bnv", x[:, :n], head[:, m])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1 + m:, None], axis=-1
        )[..., 0], axis=-1)

    # One head at a time, recomputed in the backward pass: [B, S, V] of
    # log-probabilities is the most this holds.
    per_head = [jax.checkpoint(head_nll, static_argnums=0)(m)
                for m in range(pred_heads)]
    return jnp.mean(jnp.stack(per_head), axis=0)


def per_sequence_loss(embed, layer0, rest, tokens, config):
    heads = config["num_attention_heads"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    window, chunk = config["window_size"], config["chunk_size"]
    b, s = tokens.shape

    def layer(x, p):
        d = x.shape[-1]
        hd = d // heads
        h = _rms_norm(x, p["attn_norm"], eps)
        q = _rope((h @ p["w_q"]).reshape(b, s, heads, hd), theta)
        k = _rope((h @ p["w_k"]).reshape(b, s, heads, hd), theta)
        v = (h @ p["w_v"]).reshape(b, s, heads, hd)
        a = eva_attention(q, k, v, p["phi"], p["mu"], window, chunk)
        x = x + a.reshape(b, s, d) @ p["w_o"]
        h = _rms_norm(x, p["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]

    x = embed[tokens]
    x = common.run_layers(x, layer, layer0, rest["layers"])
    x = _rms_norm(x, rest["final"]["scale"].astype(common.F32), eps)
    return multibyte_nll(
        x, rest["head"].astype(common.F32), tokens, config["num_pred_heads"]
    )
