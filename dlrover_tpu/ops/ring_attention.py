"""Ring attention — sequence/context parallelism over the ICI torus.

Capability parity with the reference's sequence-parallel attention
(``atorch/atorch/modules/distributed_transformer/distributed_attention.py:21-115``:
seq-sharded KV, micro-Q allgather + distributed softmax + reduce-scatter,
dual CUDA streams). The TPU-first design is a *ring*: every device keeps its
local Q block resident and rotates the K/V blocks around the ``seq`` mesh
axis with ``ppermute`` — XLA overlaps the collective-permute with the
attention compute of the current block, which is exactly the comm/compute
overlap the reference hand-builds with CUDA streams. Softmax is the online
(max/sum-carrying) form, so the result is exact, not approximate.

``ring_attention_shard`` is the per-device body (call it under
``shard_map``); ``ring_attention`` wraps it with ``shard_map`` over the
ambient mesh and falls back to plain attention when no ``seq`` axis exists,
so models can enable it unconditionally.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common.log import logger

_NEG_INF = -1e30


def ring_attention_shard(q, k, v, causal: bool = True,
                         axis_name: str = "seq"):
    """Per-device ring attention body (run under ``shard_map``).

    q, k, v: the device-local blocks [B, S_local, H, D]; the global sequence
    is the concatenation over the ``axis_name`` mesh axis. Exact (online
    softmax) — numerics match full attention on the gathered sequence.
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale = 1.0 / np.sqrt(d)

    q32 = q.astype(jnp.float32)
    m = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s_loc), jnp.float32)
    acc = jnp.zeros((b, s_loc, h, d), jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 1)

    perm = [(j, (j + 1) % n) for j in range(n)]
    k_cur, v_cur = k, v
    for step in range(n):
        # After `step` rotations we hold the block that originated on
        # device (my - step) mod n.
        src = (my - step) % n
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q32, k_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            # Global positions: q row r lives at my*s_loc + r, k col c at
            # src*s_loc + c. src is traced, so the mask is data-dependent —
            # fine under jit (select, not control flow).
            mask = (my * s_loc + rows) >= (src * s_loc + cols)
            logits = jnp.where(mask[None, None], logits, _NEG_INF)
        chunk_m = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, chunk_m)
        p = jnp.exp(logits - m_new[..., None])
        if causal:
            # A fully-masked block must contribute nothing even when
            # m_new is itself _NEG_INF (exp(0)=1 otherwise).
            p = jnp.where(logits <= _NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m - m_new)  # [b, h, s]
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc = acc * jnp.moveaxis(corr, 1, 2)[..., None] + pv
        m = m_new
        if step != n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / jnp.moveaxis(l_safe, 1, 2)[..., None]
    return out.astype(q.dtype)


def _ambient_mesh():
    """The mesh active at trace time (set by ``with mesh:`` in the accel
    layer's train step), or None."""
    from jax._src import mesh as mesh_lib

    mesh = mesh_lib.thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def _attn_specs(mesh, axis_name: str):
    from jax.sharding import PartitionSpec as P

    batch_axes = tuple(
        a for a in ("data", "fsdp") if a in mesh.axis_names
    )
    heads = "tensor" if "tensor" in mesh.axis_names else None
    return P(batch_axes or None, axis_name, heads, None)


def ring_attention(q, k, v, causal: bool = True, axis_name: str = "seq",
                   mesh=None):
    """Sequence-parallel attention over the ambient mesh's ``seq`` axis.

    q, k, v: GLOBAL [B, S, H, D] arrays (seq-sharded by GSPMD). Falls back
    to plain attention when the mesh has no ``seq`` axis (size > 1), so the
    same model code runs on any topology.
    """
    mesh = mesh if mesh is not None else _ambient_mesh()
    if (
        mesh is None
        or axis_name not in mesh.axis_names
        or mesh.shape[axis_name] <= 1
    ):
        from dlrover_tpu.ops.attention import reference_attention

        logger.debug(
            "ring_attention: no %r mesh axis; using plain attention",
            axis_name,
        )
        return reference_attention(q, k, v, causal=causal)
    spec = _attn_specs(mesh, axis_name)
    fn = jax.shard_map(
        lambda a, b_, c: ring_attention_shard(
            a, b_, c, causal=causal, axis_name=axis_name
        ),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
