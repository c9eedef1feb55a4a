"""8-bit blockwise-quantized Adam.

Capability parity with the reference's low-bit optimizer family
(``atorch/atorch/optimizers/low_bit/``: 4/8-bit quantized Adam states
with CUDA dequant/quant kernels). The state stores both Adam moments as
int8 with per-block fp32 absmax scales (2.03 bytes/param vs 8 for fp32
Adam) and the update runs as a **Pallas kernel**: each grid program
loads its tile of (grad, param, qm, qv, scales) into VMEM, does the
whole dequantize → update → requantize chain block-locally, and writes
(param', qm', qv', scales') — ONE HBM pass. The same chain as plain
XLA ops materializes ~5 fp32 temporaries per element (measured: 131 ms
for an 820M-param update on v5e vs 33 ms for fp32 adamw — the
optimizer was 35% of the 1.5B train step), exactly the hand-fusion
case the CUDA kernels in the reference exist for, done the TPU way.

**Every leaf is read and written where it lies.** The int8 moments have
the parameter's own shape and a block is 256 consecutive elements of a
row (the row's tail a shorter block of its own), so the kernel's
``BlockSpec``s index gradient, parameter and moments in place and the
state is updated in place (``input_output_aliases``): no re-layout of a
leaf feeds or follows the call. (Flattening a leaf into ``[N, 256]``
rows around the kernel is a reshape logically and a physical copy on
the TPU's tiled layout: measured, 1.8 × the kernel it served.) The
scales lie ``[..., blocks, rows]``, rows along the lanes: a ``[rows, 1]``
array pads every scale to 128 lanes in HBM.
The moments have the parameter's rank, which is what sharding them like
the parameter needs; nothing shards them yet.

Transient memory is bounded by the kernel's VMEM tile, so scanned
48-layer stacks update without ever materializing a layer of fp32
state — this is what lets a 1.5B model train on a single 16 GB chip.
The moments are replicated and the kernel is not mesh-partitioned, so
``auto_accelerate`` refuses it on a mesh of more than one device.
Interpreter mode is for the CPU tests only (``dlrover_tpu.ops.interpret``).
"""

import math
from functools import partial
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import interpret as interpret_mode


class _QTensor(NamedTuple):
    q: jnp.ndarray       # int8 payload, the parameter's own shape
    scale: jnp.ndarray   # fp32 absmax per block (``_scale_shape``)


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


def _padded(rows: int, width: int) -> int:
    """Elements a ``[rows, width]`` matrix takes in HBM, ``width`` along
    the 128 lanes (int8 sublane tile; the other dtypes' are finer)."""
    return pl.cdiv(rows, 32) * 32 * pl.cdiv(width, 128) * 128


def _rows(shape) -> Tuple[int, int, int, bool]:
    """A leaf as ``(L, rows, width, swapped)``: ``L`` matrices of ``rows``
    rows, leading axes merged (the TPU tiles the last two axes only, so
    that is no copy). A vector is one row. The chip stores a matrix with
    whichever of its two axes pads least along the lanes (GPT-2's
    ``[6400, 1600]`` and ``[50257, 1600]`` lie transposed in HBM), and a
    row here is what lies along the lanes there: for such a leaf the
    last two axes swap, which then costs nothing either. A wrong guess
    costs a copy, never a wrong answer."""
    if len(shape) < 2:
        return 1, 1, math.prod(shape), False
    n_lead, a, b = math.prod(shape[:-2]), shape[-2], shape[-1]
    if _padded(b, a) < _padded(a, b):
        return n_lead, b, a, True
    return n_lead, a, b, False


def _scale_shape(shape, block: int) -> Tuple[int, ...]:
    """One absmax for every run of ``block`` consecutive elements of a
    row (``_rows``), the row's tail a shorter run of its own. Stored
    ``[..., runs, rows]``: the rows lie along the lanes, so the array is
    dense in HBM and a row tile's scales are one DMA."""
    _, rows, width, _ = _rows(shape)
    runs = pl.cdiv(width, block)
    return tuple(shape[:-2]) + (runs, rows) if len(shape) >= 2 else (runs,)


_TILE_ROWS = 2048        # most rows of a leaf per pallas program
_VMEM_LIMIT = 48 << 20   # the kernel's scoped VMEM (the default is 16 MiB)
_VMEM_TILE = 36 << 20    # of it, for one program's blocks and temporaries


def _tile_rows(a: int, row_bytes: int) -> int:
    """Rows a program takes, each holding ``row_bytes`` of VMEM. A leaf
    of few rows is one tile; otherwise the multiple of 128 (the scales'
    lane tile) in the upper half of what fits that covers ``a`` with the
    fewest rows past its end, the larger first. The last tile may hang
    over: those rows are read as garbage, share no reduction with a real
    row, and their writes are dropped."""
    cap = min(_TILE_ROWS, _VMEM_TILE // row_bytes)
    if a <= cap:
        return a
    top = cap // 128 * 128
    return min(range(top, top // 2, -128), key=lambda t: pl.cdiv(a, t) * t)


def _adam8_kernel(bc_ref, g_ref, mq_ref, msc_ref, sq_ref, ssc_ref,
                  u_ref, mqo_ref, msco_ref, sqo_ref, ssco_ref,
                  *, lr, b1, b2, eps, width, wd=0.0, p_ref=None):
    """One ``[rows, block]`` tile of a leaf ``width`` wide: dequantize ->
    Adam -> requantize, all VMEM-local. The grid's last axis walks the
    row's blocks; the scale refs hold every block of these rows
    (``[blocks, rows]``) and stay put while it does.

    ``v`` is stored as sqrt(v) (see ``adam8bit``'s rationale) and
    the denominator is floored at half a quantization step *in the int
    domain* (``maximum(q, 0.5)``) — same guarantee as the reference
    implementation's explicit floor, fused for free.
    """
    j = pl.program_id(2)
    block = g_ref.shape[1]
    if width % block:
        # The row's tail: lanes past the leaf's width hold garbage that
        # must reach no absmax (what is written there is dropped).
        lane = jax.lax.broadcasted_iota(jnp.int32, g_ref.shape, 1)
        live = lane < width - j * block
        read = lambda ref: jnp.where(live, ref[...].astype(jnp.float32), 0.0)
    else:
        read = lambda ref: ref[...].astype(jnp.float32)
    bc1 = bc_ref[0, 0]
    bc2 = bc_ref[0, 1]
    # Per-element divides are the VPU's slowest ops: every scale divide
    # becomes a per-ROW reciprocal broadcast-multiplied, and the bias
    # corrections fold into two scalars, leaving one true divide per
    # element (the Adam quotient itself).
    sqrt_bc2 = jnp.sqrt(bc2)
    lr_eff = -lr * sqrt_bc2 / bc1
    eps_eff = eps * sqrt_bc2
    g = read(g_ref)
    msc = msc_ref[pl.ds(j, 1), :].T
    ssc = ssc_ref[pl.ds(j, 1), :].T
    m = read(mq_ref) * (msc * (b1 / 127.0)) + (1.0 - b1) * g
    s_prev = read(sq_ref) * (ssc / 127.0)
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
        u_ref[...] = (read(p_ref) * (1.0 - lr * wd) + u).astype(u_ref.dtype)
    else:
        u_ref[...] = u.astype(u_ref.dtype)
    msc2 = jnp.max(jnp.abs(m), axis=1, keepdims=True)
    r_m = jnp.where(msc2 == 0, 1.0, 127.0 / msc2)
    # |m|/absmax <= 1: round lands in [-127, 127] by construction.
    mqo_ref[...] = jnp.round(m * r_m).astype(jnp.int8)
    msco_ref[pl.ds(j, 1), :] = msc2.T
    sqo_ref[...] = sq2.astype(jnp.int8)
    ssco_ref[pl.ds(j, 1), :] = ssc2.T


def _adam8_fused_kernel(bc_ref, g_ref, mq_ref, msc_ref, sq_ref,
                        ssc_ref, p_ref, po_ref, mqo_ref, msco_ref,
                        sqo_ref, ssco_ref, **hyper):
    """Fused-apply arity: params in, new params out."""
    _adam8_kernel(bc_ref, g_ref, mq_ref, msc_ref, sq_ref, ssc_ref,
                  po_ref, mqo_ref, msco_ref, sqo_ref, ssco_ref,
                  p_ref=p_ref, **hyper)


def _pallas_leaf_update(g, qm: _QTensor, qv: _QTensor, bc12,
                        lr, b1, b2, eps, block, interpret,
                        p=None, wd=0.0):
    """Whole-leaf update through the kernel, every operand indexed where
    it lies and the state updated in place; returns (u, qm', qv'). With
    ``p`` given the apply is fused: the first output is the NEW param
    (and ``wd`` applies decoupled weight decay), not the update."""
    n_lead, a, width, swapped = _rows(g.shape)
    runs = pl.cdiv(width, block)
    out_dtype = g.dtype if p is None else p.dtype
    # A row in VMEM: one block of gradient, parameter in and out and four
    # int8 moments, and four float32 scales of every block, all double-
    # buffered; some ten float32 temporaries of the block's chain.
    rows = _tile_rows(a, 2 * (
        block * (g.dtype.itemsize + 2 * out_dtype.itemsize + 4) + 16 * runs
    ) + 10 * block * 4)

    def data(x):
        x = x.reshape((n_lead,) + ((width, a) if swapped else (a, width)))
        return jnp.swapaxes(x, 1, 2) if swapped else x

    def undo(x):
        return (jnp.swapaxes(x, 1, 2) if swapped else x).reshape(g.shape)

    scales = lambda x: x.reshape(n_lead, runs, a)
    data_spec = pl.BlockSpec((None, rows, block), lambda l, i, j: (l, i, j))
    scale_spec = pl.BlockSpec((None, runs, rows), lambda l, i, j: (l, 0, i))
    in_specs = [
        pl.BlockSpec((1, 2), lambda l, i, j: (0, 0)),
        data_spec, data_spec, scale_spec, data_spec, scale_spec,
    ]
    operands = [bc12, data(g), data(qm.q), scales(qm.scale),
                data(qv.q), scales(qv.scale)]
    # The moments (and the fused param) are rewritten where they lie: a
    # block is read before it is written and no other block overlaps it.
    aliases = {2: 1, 3: 2, 4: 3, 5: 4}
    hyper = dict(lr=lr, b1=b1, b2=b2, eps=eps, width=width)
    if p is not None:
        kernel = partial(_adam8_fused_kernel, wd=wd, **hyper)
        in_specs.append(data_spec)
        operands.append(data(p))
        aliases[6] = 0
    else:
        kernel = partial(_adam8_kernel, **hyper)
    like = lambda dims, dt: jax.ShapeDtypeStruct((n_lead,) + dims, dt)
    u, mq2, msc2, sq2, ssc2 = pl.pallas_call(
        kernel,
        grid=(n_lead, pl.cdiv(a, rows), runs),
        in_specs=in_specs,
        out_specs=[
            data_spec, data_spec, scale_spec, data_spec, scale_spec,
        ],
        out_shape=[
            like((a, width), out_dtype),
            like((a, width), jnp.int8),
            like((runs, a), jnp.float32),
            like((a, width), jnp.int8),
            like((runs, a), jnp.float32),
        ],
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(*operands)
    return (
        undo(u),
        _QTensor(q=undo(mq2), scale=msc2.reshape(qm.scale.shape)),
        _QTensor(q=undo(sq2), scale=ssc2.reshape(qv.scale.shape)),
    )


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
    if block_size % 128:
        raise ValueError(
            f"block_size {block_size}: a block is whole lane tiles of 128"
        )

    def init(params):
        # Strip flax partitioning boxes first: a box left wrapping a
        # _QTensor would broadcast the param's one sharding over q and
        # scale, and the scale's last two axes are the param's swapped.
        # The moments are replicated: at ~2 bytes/param that is the
        # 8-bit optimizer's single-chip memory story; under FSDP the
        # fp32 master path is the sharded one. (q has the param's shape
        # and scale its rank, so a later change can give them the
        # param's sharding axis by axis; nothing does yet.)
        import flax.linen as nn

        params = nn.meta.unbox(params)

        def qzero(p):
            return _QTensor(
                q=jnp.zeros(p.shape, jnp.int8),
                scale=jnp.zeros(
                    _scale_shape(p.shape, block_size), jnp.float32
                ),
            )

        return Adam8bitState(
            step=jnp.zeros((), jnp.int32),
            m=jax.tree_util.tree_map(qzero, params),
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
