"""8-bit blockwise-quantized Adam.

Capability parity with the reference's low-bit optimizer family
(``atorch/atorch/optimizers/low_bit/``: 4/8-bit quantized Adam states
with CUDA dequant/quant kernels). The state stores both Adam moments as
int8 with per-block fp32 absmax scales (2.03 bytes/param vs 8 for fp32
Adam) and the update runs as a **Pallas kernel**: each grid program
loads its block tile of (grad, qm, qv, scales) into VMEM, does the
whole dequantize → update → requantize chain block-locally, and writes
(update, qm', qv', scales') — ONE HBM pass. The same chain as plain
XLA ops materializes ~5 fp32 temporaries per element (measured: 131 ms
for an 820M-param update on v5e vs 33 ms for fp32 adamw — the
optimizer was 35% of the 1.5B train step), exactly the hand-fusion
case the CUDA kernels in the reference exist for, done the TPU way.

Transient memory is bounded by the kernel's VMEM tile, so scanned
48-layer stacks update without ever materializing a layer of fp32
state — this is what lets a 1.5B model train on a single 16 GB chip.
The moments are replicated and the kernel is not mesh-partitioned, so
``auto_accelerate`` refuses it on a mesh of more than one device.
Interpreter mode is for the CPU tests only (``dlrover_tpu.ops.interpret``).
"""

from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl

from dlrover_tpu.ops import interpret as interpret_mode


class _QTensor(NamedTuple):
    q: jnp.ndarray       # int8 payload, padded to a block multiple
    scale: jnp.ndarray   # fp32 absmax per block


class FusedGradientTransformation(NamedTuple):
    """optax-compatible transformation with an extra fused entry point:
    ``update_and_apply(grads, state, params) -> (new_params, state)``
    runs the optimizer AND the param update in one kernel pass, saving
    the separate ``optax.apply_updates`` HBM sweep. ``make_train_step``
    uses it when present; ``init``/``update`` keep the plain optax
    contract for everything else (checkpointing, chaining, tests)."""

    init: Any
    update: Any
    update_and_apply: Any


class Adam8bitState(NamedTuple):
    step: jnp.ndarray
    m: Any               # pytree of _QTensor (linear domain)
    v: Any               # pytree of _QTensor (SQRT domain — see below)


def _quantize(x: jnp.ndarray, block: int) -> _QTensor:
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1)
    safe = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(
        jnp.round(blocks / safe[:, None] * 127.0), -127, 127
    ).astype(jnp.int8)
    return _QTensor(q=q, scale=scale.astype(jnp.float32))


def _chunked(shape) -> bool:
    """Scanned/stacked leaves ([L, ...] from nn.scan or pipeline banks)
    quantize per leading index: the block layout (and so the state
    pytree) is per-layer, which keeps an even layer sharding's scale
    blocks device-local."""
    return len(shape) >= 3 and shape[0] > 1


_TILE = 1024  # block rows per pallas program (~3.6 MB VMEM working set)


def _adam8_kernel(bc_ref, g_ref, mq_ref, msc_ref, sq_ref, ssc_ref,
                  u_ref, mqo_ref, msco_ref, sqo_ref, ssco_ref,
                  *, lr, b1, b2, eps, wd=0.0, p_ref=None):
    """One tile: dequantize -> Adam -> requantize, all VMEM-local.

    ``v`` is stored as sqrt(v) (see ``leaf_update``'s rationale) and
    the denominator is floored at half a quantization step *in the int
    domain* (``maximum(q, 0.5)``) — same guarantee as the reference
    implementation's explicit floor, fused for free.
    """
    bc1 = bc_ref[0, 0]
    bc2 = bc_ref[0, 1]
    # Per-element divides are the VPU's slowest ops: every scale divide
    # becomes a per-ROW reciprocal broadcast-multiplied, and the bias
    # corrections fold into two scalars, leaving one true divide per
    # element (the Adam quotient itself).
    sqrt_bc2 = jnp.sqrt(bc2)
    lr_eff = -lr * sqrt_bc2 / bc1
    eps_eff = eps * sqrt_bc2
    g = g_ref[...].astype(jnp.float32)
    msc = msc_ref[...]
    ssc = ssc_ref[...]
    m = (mq_ref[...].astype(jnp.float32) * (msc * (b1 / 127.0))
         + (1.0 - b1) * g)
    s_prev = sq_ref[...].astype(jnp.float32) * (ssc / 127.0)
    v = b2 * s_prev * s_prev + (1.0 - b2) * g * g
    s = jnp.sqrt(v)
    ssc2 = jnp.max(s, axis=1, keepdims=True)
    r_s = jnp.where(ssc2 == 0, 1.0, 127.0 / ssc2)
    # s >= 0 and s/absmax <= 1, so round == floor(x + 0.5) and the
    # result is already in [0, 127]: no clip, no round-to-even lowering
    # (the VPU chain is what bounds this kernel, not DMA).
    sq2 = jnp.floor(s * r_s + 0.5)
    denom = jnp.maximum(sq2, 0.5) * (ssc2 / 127.0)
    u = lr_eff * m / (denom + eps_eff)
    if p_ref is not None:
        # Fused apply (+ decoupled weight decay): write the new params
        # directly — saves the separate apply_updates pass (u write +
        # u/p reads + p write over HBM).
        p = p_ref[...].astype(jnp.float32)
        u_ref[...] = (p * (1.0 - lr * wd) + u).astype(u_ref.dtype)
    else:
        u_ref[...] = u.astype(u_ref.dtype)
    msc2 = jnp.max(jnp.abs(m), axis=1, keepdims=True)
    r_m = jnp.where(msc2 == 0, 1.0, 127.0 / msc2)
    # |m|/absmax <= 1: round lands in [-127, 127] by construction.
    mqo_ref[...] = jnp.round(m * r_m).astype(jnp.int8)
    msco_ref[...] = msc2
    sqo_ref[...] = sq2.astype(jnp.int8)
    ssco_ref[...] = ssc2


def _adam8_fused_kernel(bc_ref, g_ref, mq_ref, msc_ref, sq_ref,
                        ssc_ref, p_ref, po_ref, mqo_ref, msco_ref,
                        sqo_ref, ssco_ref, *, lr, b1, b2, eps, wd):
    """Fused-apply arity: params in, new params out."""
    _adam8_kernel(bc_ref, g_ref, mq_ref, msc_ref, sq_ref, ssc_ref,
                  po_ref, mqo_ref, msco_ref, sqo_ref, ssco_ref,
                  lr=lr, b1=b1, b2=b2, eps=eps, wd=wd, p_ref=p_ref)


def _blocks_of(g: jnp.ndarray, block: int) -> jnp.ndarray:
    """Grad in the state's block layout: per-layer flatten + pad for
    chunked leaves (matching the vmapped ``_quantize`` of ``init``),
    plain flatten + pad otherwise."""
    if _chunked(g.shape):
        rows = g.reshape(g.shape[0], -1)
        pad = (-rows.shape[1]) % block
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
        return rows.reshape(-1, block)
    flat = g.reshape(-1)
    flat = jnp.pad(flat, (0, (-flat.size) % block))
    return flat.reshape(-1, block)


def _unblocks(u: jnp.ndarray, shape, block: int) -> jnp.ndarray:
    """Inverse of `_blocks_of`."""
    if _chunked(shape):
        L = shape[0]
        rest = 1
        for d in shape[1:]:
            rest *= d
        return u.reshape(L, -1)[:, :rest].reshape(shape)
    size = 1
    for d in shape:
        size *= d
    return u.reshape(-1)[:size].reshape(shape)


def _pallas_leaf_update(g, qm: _QTensor, qv: _QTensor, bc12,
                        lr, b1, b2, eps, block, interpret,
                        p=None, wd=0.0):
    """Whole-leaf update through the kernel; returns (u, qm', qv')
    with the state layout preserved exactly. With ``p`` given the
    apply is fused: the first output is the NEW param (and ``wd``
    applies decoupled weight decay), not the update."""
    gb = _blocks_of(g, block)
    mq = qm.q.reshape(-1, block)
    sq = qv.q.reshape(-1, block)
    msc = qm.scale.reshape(-1, 1)
    ssc = qv.scale.reshape(-1, 1)
    pb = _blocks_of(p, block) if p is not None else None
    nb = gb.shape[0]
    # Tile choice, in Mosaic-legal terms (a block's sublane dim must be
    # a multiple of 8 OR equal to the array dim):
    # - small leaves (nb <= _TILE): one whole-array block, grid of 1 —
    #   always legal, never padded;
    # - otherwise the largest power-of-two divisor of nb in [8, _TILE]
    #   (common case: divisible, zero padding, one HBM pass);
    # - awkward counts (odd embedding leaves) pad up to a full _TILE
    #   multiple (_TILE is a power of two >= 8).
    if nb <= _TILE:
        tile_rows = max(nb, 1)
    else:
        tile_rows = _TILE
        while tile_rows >= 8 and nb % tile_rows:
            tile_rows //= 2
        if tile_rows < 8:
            tile_rows = _TILE
    padn = (-nb) % tile_rows
    if padn:
        gb = jnp.pad(gb, ((0, padn), (0, 0)))
        mq = jnp.pad(mq, ((0, padn), (0, 0)))
        sq = jnp.pad(sq, ((0, padn), (0, 0)))
        msc = jnp.pad(msc, ((0, padn), (0, 0)))
        ssc = jnp.pad(ssc, ((0, padn), (0, 0)))
        if pb is not None:
            pb = jnp.pad(pb, ((0, padn), (0, 0)))
    nbp = nb + padn
    row = lambda i: (i, 0)
    tile = lambda width, dt: jax.ShapeDtypeStruct((nbp, width), dt)
    data_spec = pl.BlockSpec((tile_rows, block), row)
    scale_spec = pl.BlockSpec((tile_rows, 1), row)
    in_specs = [
        pl.BlockSpec((1, 2), lambda i: (0, 0)),
        data_spec, data_spec, scale_spec, data_spec, scale_spec,
    ]
    operands = [bc12, gb, mq, msc, sq, ssc]
    if pb is not None:
        kernel = partial(_adam8_fused_kernel, lr=lr, b1=b1, b2=b2,
                         eps=eps, wd=wd)
        in_specs.append(data_spec)
        operands.append(pb)
        out_dtype = p.dtype
    else:
        kernel = partial(_adam8_kernel, lr=lr, b1=b1, b2=b2, eps=eps)
        out_dtype = g.dtype
    u, mq2, msc2, sq2, ssc2 = pl.pallas_call(
        kernel,
        grid=(nbp // tile_rows,),
        in_specs=in_specs,
        out_specs=[
            data_spec, data_spec, scale_spec, data_spec, scale_spec,
        ],
        out_shape=[
            tile(block, out_dtype),
            tile(block, jnp.int8),
            tile(1, jnp.float32),
            tile(block, jnp.int8),
            tile(1, jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    u = _unblocks(u[:nb], g.shape, block)
    qm2 = _QTensor(
        q=mq2[:nb].reshape(qm.q.shape),
        scale=msc2[:nb].reshape(qm.scale.shape),
    )
    qv2 = _QTensor(
        q=sq2[:nb].reshape(qv.q.shape),
        scale=ssc2[:nb].reshape(qv.scale.shape),
    )
    return u, qm2, qv2


def adam8bit(
    learning_rate: float = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    block_size: int = 256,
) -> optax.GradientTransformation:
    """Adam with int8 blockwise-quantized moments (8-bit optimizer).

    The moment math (see ``_adam8_kernel``): ``v`` is stored as
    sqrt(v) — linear int8 of the squares loses small-|g| entries to a
    block's absmax quadratically faster than m does, and a v that
    underflows to 0 under a live m turns the Adam step into m/eps —
    divergence; in the sqrt domain both moments share the same
    relative resolution. The denominator is floored at half a
    quantization step of s so a moment that rounds to zero can never
    amplify m by 1/eps.
    """

    def init(params):
        # Strip flax partitioning boxes first: quantized blocks are a
        # *flattened* relayout of the param, so the param's logical axis
        # names do not apply to them — a box left wrapping a _QTensor
        # would broadcast one (rank-mismatched) sharding over q and
        # scale. The moments are replicated instead: at ~2 bytes/param
        # that is the 8-bit optimizer's single-chip memory story; under
        # FSDP the fp32 master path is the sharded one.
        import flax.linen as nn

        params = nn.meta.unbox(params)

        def qzero(p):
            z = jnp.zeros_like(p, jnp.float32)
            if _chunked(p.shape):
                return jax.vmap(partial(_quantize, block=block_size))(z)
            return _quantize(z, block_size)

        zeros = jax.tree_util.tree_map(qzero, params)
        return Adam8bitState(
            step=jnp.zeros((), jnp.int32),
            m=zeros,
            v=jax.tree_util.tree_map(qzero, params),
        )

    def _run(grads, state, params, fused):
        step = state.step + 1
        stepf = step.astype(jnp.float32)
        bc1 = 1 - b1 ** stepf
        bc2 = 1 - b2 ** stepf
        bc12 = jnp.stack([bc1, bc2]).reshape(1, 2)
        interpret = interpret_mode.use_interpret()

        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_m = treedef.flatten_up_to(state.m)
        flat_v = treedef.flatten_up_to(state.v)
        flat_p = treedef.flatten_up_to(params) if params is not None else [
            None
        ] * len(flat_g)

        new_updates, new_m, new_v = [], [], []
        for g, qm, qv, p in zip(flat_g, flat_m, flat_v, flat_p):
            u, m2, v2 = _pallas_leaf_update(
                g, qm, qv, bc12, learning_rate, b1, b2, eps,
                block_size, interpret,
                p=p if fused else None,
                wd=weight_decay,
            )
            if not fused and weight_decay and p is not None:
                u = u - (learning_rate * weight_decay * p).astype(
                    u.dtype
                )
            new_updates.append(u)
            new_m.append(m2)
            new_v.append(v2)

        return (
            jax.tree_util.tree_unflatten(treedef, new_updates),
            Adam8bitState(
                step=step,
                m=jax.tree_util.tree_unflatten(treedef, new_m),
                v=jax.tree_util.tree_unflatten(treedef, new_v),
            ),
        )

    def update(grads, state, params=None):
        return _run(grads, state, params, fused=False)

    def update_and_apply(grads, state, params):
        """Fused optimizer + apply: returns (new_params, new_state) —
        one kernel pass instead of update + apply_updates sweeps."""
        return _run(grads, state, params, fused=True)

    return FusedGradientTransformation(init, update, update_and_apply)
