"""EVA attention: causal attention inside aligned windows, joined in one
softmax with one learned summary per chunk of every earlier window
(Zheng et al. 2023, "Efficient Attention via Control Variates"; the mixer
of EvaByte). Equations: ``docs/attention_masks.md``.

Two ops. :func:`chunk_summaries` turns each chunk of ``chunk`` keys and
values into one key and one value, by a float32 softmax over the chunk's
positions against a learned per-head vector; plain ``jax.numpy``, its
backward pass is autodiff's. :func:`eva_attention` lays the summaries
before the keys and the values (``[k_bar ; k]``, ``[v_bar ; v]``) and
calls attention once under an :class:`AttentionMask` that knows which rows
are summaries: one online softmax covers both sets, and the kernel's dkv
returns the gradients of both, which autodiff splits and sends back
through the summaries.
"""

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.attention import (
    AttentionMask,
    _pick_block,
    flash_attention,
    reference_attention,
)


def chunk_summaries(k, v, phi, mu, chunk: int):
    """One summary key and value per chunk of ``chunk`` positions.

    k, v: ``[B, S, H, D]`` (k rotated); phi, mu: ``[H, D]``. For chunk
    ``c`` and head ``h``: ``a_j = softmax_j(k_j . phi_h)`` over the
    chunk's positions in float32, ``k_bar_c = sum_j a_j k_j + mu_h``,
    ``v_bar_c = sum_j a_j v_j``. Returns ``([B, S/chunk, H, D]) * 2`` in
    the inputs' dtypes.
    """
    b, s, h, d = k.shape
    if s % chunk:
        raise ValueError(f"{s} positions are not whole chunks of {chunk}")
    with jax.named_scope("attn.summaries"):
        # Head-major, as the attention kernel wants its operands anyway:
        # a chunk's positions and the head's width are the minor
        # dimensions of every sum. Products of the inputs as they are,
        # sums in float32: no float32 copy of k or v is asked for.
        f32 = {"preferred_element_type": jnp.float32}
        kc = jnp.moveaxis(k, 2, 1).reshape(b, h, s // chunk, chunk, d)
        vc = jnp.moveaxis(v, 2, 1).reshape(b, h, s // chunk, chunk, d)
        a = jax.nn.softmax(
            jnp.einsum("bhcjd,hd->bhcj", kc, phi.astype(k.dtype), **f32),
            axis=-1,
        )
        k_bar = jnp.einsum("bhcj,bhcjd->bhcd", a, kc, **f32)
        v_bar = jnp.einsum("bhcj,bhcjd->bhcd", a, vc, **f32)
        k_bar = k_bar + mu.astype(jnp.float32)[:, None, :]
        return (jnp.moveaxis(k_bar.astype(k.dtype), 1, 2),
                jnp.moveaxis(v_bar.astype(v.dtype), 1, 2))


def eva_mask(seq: int, window: int, chunk: int, block_k: int = 0):
    """The mask of an EVA call over ``seq`` positions, and how many
    summary rows it is given: ``seq / chunk`` of them, padded to whole
    blocks of ``block_k`` where a kernel wants them so (0: no padding).
    One window has no remote part and no summary row at all."""
    if seq % window or window % chunk:
        raise ValueError(
            f"EVA attention needs whole windows: {seq} positions, window "
            f"{window}, chunk {chunk} (pad or cut the sequence to a "
            f"multiple of {window})"
        )
    if seq == window:
        return AttentionMask(window=window)
    rows = seq // chunk
    if block_k:
        rows = -(-rows // block_k) * block_k
    return AttentionMask(window=window, summaries=rows, chunk=chunk)


def eva_attention(q, k, v, phi, mu, *, window: int, chunk: int,
                  impl: str = "xla", block_q: int = 512, block_k: int = 512):
    """q, k, v ``[B, S, H, D]`` (q, k rotated), phi, mu ``[H, D]`` ->
    ``[B, S, H, D]``. ``impl`` ``"pallas"`` runs the flash kernels over
    the non-empty blocks only, ``"xla"`` the dense oracle."""
    s = q.shape[1]
    pallas = impl == "pallas"
    # The key block the kernel will settle on: it divides the positions,
    # and the summary rows are padded to whole blocks of it.
    block_k = _pick_block(s, block_k) if pallas else 0
    mask = eva_mask(s, window, chunk, block_k)
    if mask.summaries:
        k_bar, v_bar = chunk_summaries(k, v, phi, mu, chunk)
        pad = ((0, 0), (0, mask.summaries - k_bar.shape[1]), (0, 0), (0, 0))
        k = jnp.concatenate([jnp.pad(k_bar, pad), k], axis=1)
        v = jnp.concatenate([jnp.pad(v_bar, pad), v], axis=1)
    with jax.named_scope("attn.mix"):
        if pallas:
            return flash_attention(
                q, k, v, mask=mask, block_q=block_q, block_k=block_k
            )
        return reference_attention(q, k, v, mask=mask)
