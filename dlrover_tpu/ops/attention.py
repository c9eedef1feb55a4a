"""Pallas TPU flash attention (forward + backward).

Blockwise online-softmax attention that never materializes the [S, S] score
matrix: O(S) memory instead of O(S^2), f32 accumulation on the MXU, causal
block skipping. Capability parity with the reference's FlashAttention
integration (``atorch/atorch/modules/transformer/layers.py:898-1661``) —
built as a native TPU kernel rather than a CUDA-library wrapper.

Layout convention matches the models: ``[batch, seq, heads, head_dim]``.
Internally arrays are folded to ``[batch*heads, seq, head_dim]``; the grid
walks (bh, q_block, kv_block) with the kv dimension innermost so the f32
accumulators live in VMEM scratch across kv steps (TPU grids execute
sequentially — the canonical Pallas accumulation pattern).

Mosaic kernels cannot be partitioned by GSPMD, so over a mesh of more than
one device the public entry point wraps the kernel in ``shard_map`` (batch
over ``data``/``fsdp``, heads over ``tensor``); models can enable
``attn_impl="pallas"`` under any ``ParallelSpec``. Interpreter mode is for
the CPU tests only (``dlrover_tpu.ops.interpret``).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from dlrover_tpu.ops import interpret as interpret_mode
from dlrover_tpu.ops.ring_attention import _ambient_mesh

_NEG_INF = -1e30
_LANES = 128  # scratch rows are padded to a full lane tile


def reference_attention(q, k, v, causal: bool = True):
    """Einsum softmax attention — the numerics oracle for the kernels.

    q, k, v: [B, S, H, D]; returns [B, S, H, D].
    """
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), s_k - s_q)
        logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


def _pick_block(seq: int, want: int) -> int:
    """Largest block <= `want` that divides `seq` (power-of-two stepping)."""
    b = min(want, seq)
    while seq % b:
        b //= 2
    return max(b, 1)


# ---------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                scale, causal, block_q, block_k, nk):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    # Causal: a kv block strictly above the diagonal contributes nothing.
    if causal:
        run = ki * block_k <= qi * block_q + block_q - 1
    else:
        run = ki >= 0  # traced always-true (pl.when needs a traced pred)

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        if causal:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            ) + qi * block_q
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            ) + ki * block_k
            logits = jnp.where(rows >= cols, logits, _NEG_INF)
        m_prev = m_s[:, 0]
        chunk_m = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m_prev, chunk_m)
        p = jnp.exp(logits - m_new[:, None])
        if causal:
            p = jnp.where(logits <= _NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_s[:, 0] = l_s[:, 0] * corr + jnp.sum(p, axis=-1)
        m_s[:, 0] = m_new
        pv = jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc[:] = acc[:] * corr[:, None] + pv

    @pl.when(ki == nk - 1)
    def _():
        l = l_s[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / l_safe[:, None]).astype(o_ref.dtype)
        # lse blocks span the full row (TPU tiling forbids a (1, block_q)
        # block over [B*H, S]); each qi writes its slice.
        lse_ref[0, 0, pl.dslice(qi * block_q, block_q)] = (
            m_s[:, 0] + jnp.log(l_safe)
        )


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    scale = 1.0 / np.sqrt(d)
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, sk, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, sk, d)
    nq, nk = sq // block_q, sk // block_k

    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
    ]

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, sq), lambda bh, qi, ki: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(qf, kf, vf)
    return jnp.moveaxis(o.reshape(b, h, sq, d), 1, 2), lse


# ---------------------------------------------------------------- backward


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc, *, scale, causal, block_q, block_k, nk):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    if causal:
        run = ki * block_k <= qi * block_q + block_q - 1
    else:
        run = ki >= 0  # traced always-true (pl.when needs a traced pred)

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            ) + qi * block_q
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            ) + ki * block_k
            logits = jnp.where(rows >= cols, logits, _NEG_INF)
        lse = lse_ref[0, 0, pl.dslice(qi * block_q, block_q)]
        p = jnp.exp(logits - lse[:, None])
        if causal:
            p = jnp.where(logits <= _NEG_INF / 2, 0.0, p)
        dp = jax.lax.dot_general(
            do_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        delta = delta_ref[0, 0, pl.dslice(qi * block_q, block_q)]
        ds = p * (dp - delta[:, None])
        acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale, causal, block_q, block_k, nq):
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if causal:
        run = ki * block_k <= qi * block_q + block_q - 1
    else:
        run = ki >= 0  # traced always-true (pl.when needs a traced pred)

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        if causal:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            ) + qi * block_q
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            ) + ki * block_k
            logits = jnp.where(rows >= cols, logits, _NEG_INF)
        lse = lse_ref[0, 0, pl.dslice(qi * block_q, block_q)]
        p = jnp.exp(logits - lse[:, None])
        if causal:
            p = jnp.where(logits <= _NEG_INF / 2, 0.0, p)
        do = do_ref[0].astype(jnp.float32)
        # dv += p^T @ do
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        delta = delta_ref[0, 0, pl.dslice(qi * block_q, block_q)]
        ds = p * (dp - delta[:, None])
        # dk += ds^T @ (q * scale)  — q already carries the scale
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    scale = 1.0 / np.sqrt(d)
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, sk, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, sk, d)
    dof = jnp.moveaxis(g, 2, 1).reshape(b * h, sq, d)
    of = jnp.moveaxis(o, 2, 1).reshape(b * h, sq, d)
    nq, nk = sq // block_q, sk // block_k
    # delta = rowsum(do * o): cheap elementwise — XLA fuses it fine.
    delta = jnp.sum(
        dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1
    )[:, None, :]  # [B*H, 1, S] — matches the lse layout

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, nk=nk,
        ),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, sq), lambda bh, qi, ki: (bh, 0, 0)),
            pl.BlockSpec((1, 1, sq), lambda bh, qi, ki: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, nq=nq,
        ),
        grid=(b * h, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, 1, sq), lambda bh, ki, qi: (bh, 0, 0)),
            pl.BlockSpec((1, 1, sq), lambda bh, ki, qi: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    unfold = lambda x, s: jnp.moveaxis(x.reshape(b, h, s, d), 1, 2)
    return unfold(dq, sq), unfold(dk, sk), unfold(dv, sk)


# ---------------------------------------------------------------- public


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, block_q, block_k, interpret):
    o, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return o


def _flash_attention_fwd(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_attention_bwd(causal, block_q, block_k, interpret, res, g):
    return _flash_bwd(causal, block_q, block_k, interpret, res, g)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def flash_attention_shard(q, k, v, causal: bool = True,
                          block_q: int = 512, block_k: int = 512,
                          interpret: Optional[bool] = None):
    """The kernel on device-local [B, S, H, D] blocks (differentiable).

    Call it directly on one device or inside a ``shard_map`` body;
    ``interpret=None`` follows ``dlrover_tpu.ops.interpret``.
    """
    if interpret is None:
        interpret = interpret_mode.use_interpret()
    return _flash_attention(q, k, v, causal, block_q, block_k, interpret)


def _shard_spec(mesh, shape) -> P:
    """Batch over ``data``/``fsdp`` and heads over ``tensor``, each only
    where it divides evenly (``shard_map`` cannot pad the way GSPMD
    does); every device sees the whole sequence."""
    batch_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
    if shape[0] % int(np.prod([mesh.shape[a] for a in batch_axes])):
        batch_axes = ()
    heads = None
    if "tensor" in mesh.axis_names and shape[2] % mesh.shape["tensor"] == 0:
        heads = "tensor"
    return P(batch_axes or None, None, heads, None)


def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: Optional[bool] = None,
                    mesh=None):
    """Flash attention over GLOBAL [B, S, H, D] inputs (differentiable).

    Over the ambient mesh (or ``mesh``) of more than one device the
    kernel runs under ``shard_map``; on one device it is called bare.
    """
    kernel = functools.partial(
        flash_attention_shard, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )
    mesh = mesh if mesh is not None else _ambient_mesh()
    if mesh is None or mesh.size == 1:
        return kernel(q, k, v)
    spec = _shard_spec(mesh, q.shape)
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
