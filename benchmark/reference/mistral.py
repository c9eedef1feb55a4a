"""Mistral-7B, plainly (Jiang et al. 2023, arXiv:2310.06825, and the
published reference implementation ``mistral-inference``): pre-RMSNorm
blocks, rotary position embedding on q and k over adjacent pairs of the
head dimension (the complex-number form of the reference implementation),
grouped-query attention, SiLU-gated MLP, a final RMSNorm, an untied output
head, no biases. v0.3 has no sliding window: attention is full and causal.
"""

import jax
import jax.numpy as jnp

from benchmark.reference import common

TOLERANCE = {
    # As in reference/gpt2.py. Chip runs of PR 22 (two seeds): loss gaps
    # 2e-4 and 1e-3; block 0 cosine 0.999963-0.999964, norm ratio
    # 0.99988-0.99998; embedding cosine 0.9984-0.9986, ratio 0.982-0.984.
    "loss_abs": 0.01,
    "layer0": {"cosine_min": 0.9999, "norm_ratio": [0.99, 1.01]},
    "embed": {"cosine_min": 0.997, "norm_ratio": [0.95, 1.05]},
}


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


def per_sequence_loss(embed, layer0, rest, tokens, config):
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    b, s = tokens.shape

    def layer(x, p):
        d = x.shape[-1]
        hd = d // heads
        h = _rms_norm(x, p["attn_norm"], eps)
        q = _rope((h @ p["w_q"]).reshape(b, s, heads, hd), theta)
        k = _rope((h @ p["w_k"]).reshape(b, s, kv, hd), theta)
        v = (h @ p["w_v"]).reshape(b, s, kv, hd)
        # Query head j reads key/value head j // (heads / kv).
        k = jnp.repeat(k, heads // kv, axis=2)
        v = jnp.repeat(v, heads // kv, axis=2)
        a = common.causal_attention(q, k, v).reshape(b, s, d)
        x = x + a @ p["w_o"]
        h = _rms_norm(x, p["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]

    x = embed[tokens]
    x = common.run_layers(x, layer, layer0, rest["layers"])
    x = _rms_norm(x, rest["final"]["scale"].astype(common.F32), eps)
    return common.sequence_nll(x, rest["head"].astype(common.F32), tokens)
