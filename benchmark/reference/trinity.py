"""Trinity (``afmoe``), plainly: one chip's share of Arcee's
Trinity-Large-Preview (``config.json`` of arcee-ai/Trinity-Large-Preview on
the Hugging Face hub; the parts that are no keys of it from the ``afmoe``
modelling code of ``transformers``, each under ``assumed`` in
``configs/trinity-large-preview.json``).

    h   = E[tokens] * sqrt(d)
    h   = h + N2(Attn_l(N1(h)));   h = h + N4(FFN_l(N3(h)))     (RMSNorms, stored scale)
    q, k, v, g = x Wq, x Wk, x Wv, x Wg;   q, k = RMSNorm_D(q), RMSNorm_D(k)
    sliding layer: q, k rotated (RoPE), query i sees i - W < j <= i
    full layer:    no position encoding,  query i sees j <= i
    Attn = (softmax(q k^T / sqrt D) v * sigmoid(g)) Wo          (48 query heads on 8 k/v heads)
    FFN_l, l < dense layers:  SwiGLU of width intermediate_size
    FFN_l otherwise:  s = sigmoid(x Wr) over all E routed experts;  C = top4(s + b)
                      b = b_64,  b_0 = 0,  b_{i+1} = b_i + 0.1 * 0.9^i * sign(N * 4 / E - load_e(b_i)),
                      load_e(b) the tokens among the N at hand with e in top4(s + b)     (no gradient)
                      w_e = route_scale * s_e / (sum_{c in C} s_c + 1e-20)
                      Shared(x) + sum_{e in C, e held} w_e Expert_e(x)     (SwiGLUs of width moe_intermediate_size)
    loss = mean next-token NLL over the rows of the vocabulary held

The chip holds experts 0 .. num_experts - 1 of the E = reduced_from.num_experts
that the router scores; the terms of the experts it does not hold are the
other chips', and are in neither this file's result nor the program's.
Every held expert is computed for every token and masked: dropless, and
nothing like the program's pair buffer.

Everything is float32 under ``highest``; the masks are dense; queries go
through the keys in blocks, for memory only.
"""

import jax
import jax.numpy as jnp

from benchmark.reference import common

TOLERANCE = {
    # Chip readings at the published widths (my chip runs, PR 34; TPU v5
    # lite; PERF.md section 6), the router balanced as in this file's head:
    # what the cell read over nine seeds (the gradient sample: 5120 tokens,
    # a sliding window and a quarter; "layer0" is the leading dense layer
    # and the first expert layer together), and what the same comparison
    # read on two of those seeds with the int8 MLP of the dense layer, the
    # nearest precision below the stated bfloat16, which must fail.
    #
    # First-step loss of the 1 x 16384 batch against the reference's:
    # |gap| 2.3e-4 to 7.9e-4 (the sample's 9e-5 to 1.8e-3). The int8 MLP
    # moves it as little (9e-5, 1.2e-3), so the loss tells a wrong program,
    # not a precision: the limit of the accepted cells, five times the
    # largest reading.
    "loss_abs": 0.01,
    # 1 - cosine: the embedding read 2.03e-5 to 3.57e-5, int8 6.99e-5 and
    # 7.34e-5; the two layers 1.80e-5 to 3.10e-5, int8 6.46e-5 and 6.47e-5.
    # The limit 5e-5 is 1.4 times the largest reading and 1.3 to 1.4 times
    # under int8's smallest: the two lie 2.0 to 2.1 times apart (on one
    # seed 2.3 to 2.7), because the int8 MLP is one layer's of five. Router
    # scores in bfloat16 are no control here: 3.57e-5 and 4.08e-5, 1.3
    # times their seeds' own readings.
    # Norm ratio: the two layers read 0.99980 to 1.00033, the embedding
    # 0.99979 to 1.00076; int8 reads the same (0.99956 to 1.00091): the
    # cosine is the limit that tells it apart. 2.5e-3 from 1, three times
    # the largest reading, as the accepted cells'.
    "layer0": {"cosine_min": 0.99995, "norm_ratio": [0.9975, 1.0025]},
    "embed": {"cosine_min": 0.99995, "norm_ratio": [0.9975, 1.0025]},
}
QUERY_BLOCK = 256       # [heads, 256, S] float32 scores at a time


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * scale


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


def grouped_attention(q, k, v, window, query_block: int = QUERY_BLOCK):
    """Softmax attention of ``q`` ``[B, S, H, D]`` on ``k``, ``v``
    ``[B, S, G, D]`` (query head h reads k/v head h // (H / G)); query i
    sees keys ``j <= i`` and, with ``window`` > 0, ``j > i - window``."""
    b, s, h, d = q.shape
    g = k.shape[2]
    q = q.reshape(b, s, g, h // g, d)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, common.F32))
    cols = jnp.arange(s)

    def block(start, q_rows):
        rows = start + jnp.arange(q_rows.shape[1])
        seen = cols[None, :] <= rows[:, None]
        if window:
            seen &= cols[None, :] > rows[:, None] - window
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q_rows, k) * scale
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p, v)

    if s <= query_block:
        return block(0, q).reshape(b, s, h, d)
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
    )  # [n, B, query_block, G, H / G, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def balance(scores, top, rounds=64, step=0.1, decay=0.9):
    """The bias of the choice at which the tokens of ``scores`` ``[N, E]``
    load the experts alike: the recurrence in this file's head."""
    scores = jax.lax.stop_gradient(scores)
    n, experts = scores.shape

    def next_bias(i, b):
        _, chosen = jax.lax.top_k(scores + b, top)
        load = jnp.sum(jax.nn.one_hot(chosen, experts, dtype=common.F32), (0, 1))
        return b + step * decay ** i * jnp.sign(n * top / experts - load)

    return jax.lax.fori_loop(
        0, rounds, next_bias, jnp.zeros((experts,), common.F32)
    )


def held_experts(x, p, config):
    """The chip's share of the routed FFN over tokens ``x`` ``[N, d]``:
    the shared expert, and of each token's chosen experts those held."""
    top = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ p["router"])                    # [N, E]
    _, chosen = jax.lax.top_k(scores + balance(scores, top), top)
    total = jnp.sum(jnp.take_along_axis(scores, chosen, -1), -1) + 1e-20
    y = _swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    for e in range(p["w_gate"].shape[0]):       # the experts held: 0, 1, ..
        weight = jnp.where(
            jnp.any(chosen == e, axis=-1),
            config["route_scale"] * scores[:, e] / total, 0.0,
        )
        y = y + weight[:, None] * _swiglu(
            x, p["w_gate"][e], p["w_up"][e], p["w_down"][e]
        )
    return y


def layer(x, p, kind, config):
    """One layer over ``x`` ``[B, S, d]``: ``kind`` is the layer's entry of
    ``layer_types``; its FFN is dense where ``p`` has no router."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    b, s, d = x.shape
    h = _rms_norm(x, p["attn_norm"], eps)
    q = _rms_norm((h @ p["w_q"]).reshape(b, s, heads, hd), p["q_norm"], eps)
    k = _rms_norm((h @ p["w_k"]).reshape(b, s, kv, hd), p["k_norm"], eps)
    v = (h @ p["w_v"]).reshape(b, s, kv, hd)
    sliding = kind == "sliding_attention"
    if sliding:
        theta = float(config["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
    o = grouped_attention(
        q, k, v, config["sliding_window"] if sliding else 0
    ).reshape(b, s, heads * hd)
    a = (o * jax.nn.sigmoid(h @ p["w_g"])) @ p["w_o"]
    x = x + _rms_norm(a, p["attn_post_norm"], eps)
    h = _rms_norm(x, p["mlp_norm"], eps)
    if "router" in p:
        f = held_experts(h.reshape(b * s, d), p, config).reshape(b, s, d)
    else:
        f = _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    return x + _rms_norm(f, p["mlp_post_norm"], eps)


def per_sequence_loss(embed, layer0, rest, tokens, config):
    """``layer0`` holds the leading dense layer (``d.*``) and the first
    expert layer (``e.*``) in float32: the gradients compared are the
    embedding's and theirs. ``rest["more"]`` lists the later layers in
    the program's precision, cast to float32 one at a time."""
    kinds = config["layer_types"]
    cast = lambda p: {k: w.astype(common.F32) for k, w in p.items()}
    block = jax.checkpoint(
        lambda x, p, kind: layer(x, cast(p), kind, config),
        static_argnums=2,
    )
    x = embed[tokens] * jnp.sqrt(jnp.asarray(embed.shape[-1], common.F32))
    for prefix, kind in zip(("d.", "e."), kinds):
        x = block(x, {k[2:]: w for k, w in layer0.items()
                      if k.startswith(prefix)}, kind)
    for p, kind in zip(rest["more"], kinds[2:]):
        x = block(x, p, kind)
    x = _rms_norm(
        x, rest["final"]["scale"].astype(common.F32), config["rms_norm_eps"]
    )
    return common.sequence_nll(x, rest["head"].astype(common.F32), tokens)
