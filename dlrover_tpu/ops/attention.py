"""Pallas TPU flash attention (forward + backward).

Blockwise online-softmax attention that never materializes the [S, S] score
matrix: O(S) memory instead of O(S^2), f32 accumulation on the MXU, causal
block skipping. Capability parity with the reference's FlashAttention
integration (``atorch/atorch/modules/transformer/layers.py:898-1661``) —
built as a native TPU kernel rather than a CUDA-library wrapper.

Layout convention matches the models: ``[batch, seq, heads, head_dim]``.
Internally arrays are folded to ``[batch*heads, seq, head_dim]``; the grid
walks (bh, step) with a row of blocks' steps one after another so the f32
accumulators live in VMEM scratch across them (TPU grids execute
sequentially — the canonical Pallas accumulation pattern).

What a query may see is a static :class:`AttentionMask` (plain, causal,
aligned causal windows optionally joined in the same softmax with leading
rows of chunk summaries, or a window that slides with the query,
``docs/attention_masks.md``). Every mask walks a *schedule* derived from
it: the list of the non-empty blocks, handed to the kernels as prefetched
scalars, and for each block the sub-tiles (``_SUB_TILE`` square) that are
live, and whether the mask's edge cuts a strip of them. A kernel body
computes the live sub-tiles only and evaluates the mask only in a cut
strip.

Mosaic kernels cannot be partitioned by GSPMD, so over a mesh of more than
one device the public entry point wraps the kernel in ``shard_map`` (batch
over ``data``/``fsdp``, heads over ``tensor``); models can enable
``attn_impl="pallas"`` under any ``ParallelSpec``. Interpreter mode is for
the CPU tests only (``dlrover_tpu.ops.interpret``).
"""

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from dlrover_tpu.ops import interpret as interpret_mode
from dlrover_tpu.ops.ring_attention import _ambient_mesh
from dlrover_tpu.utils.tracing import get_tracer

_NEG_INF = -1e30
_LANES = 128  # a row's running statistics are kept in every lane of a tile
#: Side of the square sub-tile a kernel body computes at a time, where it
#: divides the block (else the largest common divisor that does): the unit
#: in which a block the mask's edge cuts is skipped, run bare or masked
#: (``docs/attention_masks.md`` has the sweep it was chosen by).
_SUB_TILE = 256


@dataclasses.dataclass(frozen=True)
class AttentionMask:
    """Which keys a query sees. Static and hashable: the kernels' block
    skipping and in-block mask, the dense oracle and the pair counts are
    all derived from this one description.

    - ``causal`` alone: query ``i`` sees keys ``j <= i`` (aligned at the
      end where there are more keys than queries).
    - ``window`` > 0 (causal): query ``i`` sees ``j <= i`` of its own
      aligned window ``i // window`` only.
    - ``window`` > 0 with ``sliding``: query ``i`` sees the ``window``
      keys ``i - window < j <= i``, its own among them; the window moves
      with the query, so sequences need not be whole windows and blocks
      need not divide one.
    - ``summaries`` > 0 (with ``window`` and ``chunk``): the first
      ``summaries`` rows of k/v are no positions but one summary per
      ``chunk`` positions, in order (rows past ``positions / chunk`` are
      padding, seen by no query); position ``j`` is row ``summaries +
      j``. Query ``i`` sees the summaries of every chunk of every earlier
      window and none of its own. Both sets share one softmax.
    """

    causal: bool = True
    window: int = 0
    summaries: int = 0
    chunk: int = 0
    sliding: bool = False

    def __post_init__(self):
        if self.window and not self.causal:
            raise ValueError("a windowed mask is causal inside its windows")
        if self.sliding and (not self.window or self.summaries):
            raise ValueError(
                "a sliding mask needs a window and takes no summary rows"
            )
        if self.summaries and not (
            self.window and self.chunk and self.window % self.chunk == 0
        ):
            raise ValueError(
                f"summary rows need a window ({self.window}) that is a "
                f"multiple of their chunk ({self.chunk})"
            )

    def bounds(self, rows, s_q: int, s_k: int):
        """``(lo, hi, n)``: query ``rows`` sees the key rows ``lo <= col
        <= hi`` (positions) and ``col < n`` (summaries). Works alike on
        numpy and on traced integers."""
        if not self.window:
            return 0 * rows, rows + (s_k - s_q), 0 * rows
        if self.sliding:
            lo = rows - (self.window - 1)
            return lo * (lo > 0), rows, 0 * rows
        first = rows // self.window
        per_window = self.window // self.chunk if self.summaries else 0
        return (first * self.window + self.summaries, rows + self.summaries,
                first * per_window)

    def dense(self, s_q: int, s_k: int):
        """The whole mask ``[s_q, s_k]`` as booleans (the oracle's)."""
        if not self.causal:
            return jnp.ones((s_q, s_k), dtype=bool)
        rows, cols = jnp.arange(s_q)[:, None], jnp.arange(s_k)[None, :]
        lo, hi, n = self.bounds(rows, s_q, s_k)
        return ((cols >= lo) & (cols <= hi)) | (cols < n)

    def piece(self, row0, col0, n_rows: int, n_cols: int,
              keys_first: bool = False):
        """The mask of the ``n_rows`` queries from ``row0`` over the
        ``n_cols`` key rows from ``col0`` inside a kernel, ``[n_rows,
        n_cols]`` or with ``keys_first`` its transpose. A windowed mask's
        piece lies whole among the summaries or whole among the positions
        (a block, and so a sub-tile, divides ``summaries``), so one pair
        of bounds a row decides it."""
        q_dim, k_dim = (1, 0) if keys_first else (0, 1)

        def index(dim, shape):
            if keys_first:
                shape = shape[::-1]
            return jax.lax.broadcasted_iota(jnp.int32, shape, dim)

        if not self.window:
            rows = index(q_dim, (n_rows, n_cols)) + row0
            cols = index(k_dim, (n_rows, n_cols)) + col0
            return rows >= cols
        rows = index(q_dim, (n_rows, 1)) + row0
        cols = index(k_dim, (1, n_cols)) + col0
        lo, hi, n = self.bounds(rows, 0, 0)
        if self.summaries:
            among = col0 < self.summaries
            lo, hi = jnp.where(among, 0, lo), jnp.where(among, n - 1, hi)
        return (cols >= lo) & (cols <= hi)

    def tiles(self, s_q: int, s_k: int, block_q: int, block_k: int):
        """``(live, whole)``, each ``[nq, nk]`` numpy booleans: the tiles
        (blocks, or a block's sub-tiles) in which some query sees some
        key, and those in which every query sees every key. A live tile
        that is not whole is *cut* by an edge of the mask."""
        nq, nk = s_q // block_q, s_k // block_k
        if not self.causal:
            return (np.ones((nq, nk), dtype=bool),) * 2
        lo, hi, n = self.bounds(
            np.arange(s_q).reshape(nq, block_q), s_q, s_k
        )
        first = np.arange(nk)[None, :] * block_k       # a block's first row
        last = first + block_k - 1
        live = (first <= hi.max(1)[:, None]) & (last >= lo.min(1)[:, None])
        whole = (first >= lo.max(1)[:, None]) & (last <= hi.min(1)[:, None])
        return (live | (first < n.max(1)[:, None]),
                whole | (last < n.min(1)[:, None]))

    def live_blocks(self, s_q: int, s_k: int, block_q: int, block_k: int):
        """``[nq, nk]`` numpy booleans: the blocks in which some query
        sees some key. The kernels run exactly these."""
        return self.tiles(s_q, s_k, block_q, block_k)[0]

    def pairs(self, s_q: int, s_k: int) -> int:
        """Query-key pairs the mask allows (one head of one sequence)."""
        if not self.causal:
            return s_q * s_k
        lo, hi, n = self.bounds(np.arange(s_q, dtype=np.int64), s_q, s_k)
        return int(np.sum(np.maximum(hi - lo + 1, 0) + n))


def _as_mask(causal: Optional[bool],
             mask: Optional[AttentionMask]) -> AttentionMask:
    """The one description a public call works with: ``mask``, or the
    plain mask that the bool ``causal`` names (causal where neither is
    given). Both at once say one thing twice, or two things: refused."""
    if mask is None:
        return AttentionMask(causal=causal is None or bool(causal))
    if causal is not None:
        raise ValueError(
            f"give causal={causal!r} or mask={mask!r}, not both: the "
            "mask says whether it is causal"
        )
    return mask


def reference_attention(q, k, v, causal: Optional[bool] = None,
                        mask: Optional[AttentionMask] = None):
    """Einsum softmax attention — the numerics oracle for the kernels.

    q, k, v: [B, S, H, D]; returns [B, S, H, D]. What a query sees is
    ``mask`` (an :class:`AttentionMask`) or the bool ``causal`` (default:
    causal), never both.
    """
    mask = _as_mask(causal, mask)
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask.causal:
        logits = jnp.where(
            mask.dense(q.shape[1], k.shape[1]), logits, _NEG_INF
        )
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


def _pick_block(seq: int, want: int) -> int:
    """Largest block <= `want` that divides `seq` (power-of-two stepping)."""
    b = min(want, seq)
    while seq % b:
        b //= 2
    return max(b, 1)


def pick_blocks(mask: AttentionMask, s_q: int, s_k: int,
                block_q: int, block_k: int) -> tuple:
    """The blocks a call runs with. A block that does not divide the
    sequence is halved until it does; one that the mask needs whole
    inside a window is refused instead (a block that divides the window
    and the key rows divides the summary rows too: a key block lies whole
    among the summaries or whole among the positions)."""
    block_q, block_k = _pick_block(s_q, block_q), _pick_block(s_k, block_k)
    if mask.causal and not mask.window and s_q != s_k:
        # The kernels' causal rule, in a block and between blocks, counts
        # rows and columns from the start; the description (`bounds`: the
        # oracle, `live_blocks`, the pair counts) aligns them at the end.
        # They are one rule only where there is a key row a query.
        raise ValueError(
            f"the causal kernels want one key row a query, not {s_q} "
            f"queries and {s_k} keys"
        )
    if mask.sliding:
        if s_k != s_q:
            raise ValueError(
                f"a sliding mask wants one key row a query, not {s_q} "
                f"queries and {s_k} keys"
            )
    elif mask.window:
        if s_q % mask.window or s_k != mask.summaries + s_q:
            raise ValueError(
                f"{s_q} queries are not whole windows of {mask.window}, or "
                f"{s_k} key rows are not {mask.summaries} summaries and "
                "one row a query"
            )
        for what, block in (("block_q", block_q), ("block_k", block_k)):
            if mask.window % block:
                raise ValueError(
                    f"{what} {block} does not divide the attention window "
                    f"{mask.window}: choose a block that does"
                )
    return block_q, block_k


# ------------------------------------------------- where a grid step is


def sub_tile(block_q: int, block_k: int) -> int:
    """Side of the square sub-tile the kernels compute at a time in blocks
    of this shape: ``_SUB_TILE`` where it divides both sides."""
    return math.gcd(block_q, block_k, _SUB_TILE)


def _run_of(flags) -> tuple:
    """``(first, last + 1)`` of the one run of set entries, ``(0, 0)``
    where none is set. The masks' bounds rise with the query, so what a
    strip of sub-tiles sees is one run; a description that breaks this is
    refused here and not computed wrongly."""
    found = np.flatnonzero(flags)
    if not found.size:
        return 0, 0
    if found[-1] - found[0] + 1 != found.size:
        raise ValueError(f"the sub-tiles of a strip are no single run: {flags}")
    return int(found[0]), int(found[-1]) + 1


def _strip(live, whole) -> tuple:
    """``(a, b, cut)`` of a strip: its sub-tiles ``[a, b)`` are live, and
    ``cut`` where an edge of the mask crosses one of them."""
    a, b = _run_of(live)
    return a, b, bool((live & ~whole).any())


def _schedule(mask, s_q, s_k, block_q, block_k, t, kv_major: bool) -> tuple:
    """The non-empty blocks of a mask in the order a kernel walks them,
    and what of each block is to be computed, in sub-tiles of side ``t``
    (:func:`sub_tile`'s): three int32 vectors (the query block, the key
    block, and flags: 1 first of its row of blocks, 2 last of it, the
    rest ``class << 2``) and the classes, class ``n`` the ``n``-th of
    them and 0 no compute. A class says for each strip of a block ``(a,
    b, cut)``: its sub-tiles ``[a, b)`` are live, and with ``cut`` an
    edge of the mask crosses some of them (else no pair of the strip's
    run is masked). A strip is a sub-row of queries over the block's
    keys, or with ``kv_major`` (dkv: a row of blocks is a key block's,
    else a query block's) a sub-column of keys over its queries. The
    blocks of one mask fall into a few classes whatever the length (all
    of it; the triangle under the diagonal; over a window's trailing
    edge; the first columns of summary rows), and all of it is the
    mask's ``tiles`` at the sub-tile's size, folded by block. A row
    with no live block still gets one step, without compute, so that its
    result is written (zeros)."""
    nq, nk = s_q // block_q, s_k // block_k
    live, whole = (
        x.reshape(nq, block_q // t, nk, block_k // t).transpose(0, 2, 1, 3)
        for x in mask.tiles(s_q, s_k, t, t)
    )   # [query block, key block, sub-row, sub-column]
    if kv_major:
        live, whole = (x.transpose(1, 0, 3, 2) for x in (live, whole))
    outer, inner, flags, classes = [], [], [], []
    for o, row in enumerate(live.any(axis=(2, 3))):
        found = np.flatnonzero(row)
        steps = found if found.size else [0]
        for n, i in enumerate(steps):
            outer.append(o)
            inner.append(i)
            number = 0
            if found.size:
                kind = tuple(map(_strip, live[o, i], whole[o, i]))
                if kind not in classes:
                    classes.append(kind)
                number = classes.index(kind) + 1
            flags.append((n == 0) + 2 * (n == len(steps) - 1) + 4 * number)
    qs, ks = (inner, outer) if kv_major else (outer, inner)
    return tuple(np.asarray(x, dtype=np.int32)
                 for x in (qs, ks, flags)), tuple(classes)


class _Step:
    """Where a grid step is, read from the prefetched vectors of
    :func:`_schedule`: its query block and key block, whether it is the
    first or the last of its row of blocks, and its block's class."""

    def __init__(self, sched, classes, tile: int):
        at = pl.program_id(1)
        self.qi, self.ki, self.flags = (ref[at] for ref in sched)
        self.classes, self.tile = classes, tile

    def first(self):
        return (self.flags & 1) != 0

    def last(self):
        return (self.flags & 2) != 0

    def pieces(self, piece):
        """``piece(s, i, strip, along, cut)`` for every strip ``s`` of
        the step's block that has a live sub-tile, in one piece: ``strip``
        the strip's slice of the block's side, ``along`` its live
        sub-tiles' slice of the other side, beginning at sub-tile ``i``;
        ``cut`` where an edge of the mask crosses the piece. One straight
        body a class, so that a strip's products run beside its
        neighbour's softmax; a step of no class computes nothing."""
        t = self.tile

        def body(kind):
            for s, (a, b, cut) in enumerate(kind):
                if b > a:
                    piece(s, a, pl.ds(s * t, t), pl.ds(a * t, (b - a) * t),
                          cut)

        for number, kind in enumerate(self.classes, 1):
            pl.when(self.flags >> 2 == number)(functools.partial(body, kind))


def _call(mask, kernel, dims, kv_major, ins, outs, out_shape, scratch,
          interpret, operands):
    """One ``pallas_call`` over the grid ``(bh, steps)`` with the schedule
    as prefetched scalars, its classes and the sub-tile's side as the
    kernel's ``classes`` and ``t``; ``dims`` is ``(bh, s_q, s_k, block_q,
    block_k, t)``.
    ``ins``/``outs`` give each operand's block shape and what indexes it:
    the query block (``"q"``), the key block (``"k"``) or nothing
    (``"row"``: a whole lse row)."""
    bh, s_q, s_k, block_q, block_k, t = dims
    sched, classes = _schedule(mask, s_q, s_k, block_q, block_k, t, kv_major)
    index = {"q": lambda bh, at, qs, ks, fl: (bh, qs[at], 0),
             "k": lambda bh, at, qs, ks, fl: (bh, ks[at], 0),
             "row": lambda bh, at, qs, ks, fl: (bh, 0, 0)}

    def spec(shape_by):
        return pl.BlockSpec(shape_by[0], index[shape_by[1]])

    return pl.pallas_call(
        lambda qs, ks, fl, *refs: kernel((qs, ks, fl), *refs, t=t,
                                         classes=classes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(bh, len(sched[0])),
            in_specs=[spec(x) for x in ins],
            out_specs=[spec(x) for x in outs] if isinstance(outs, list) else (
                spec(outs)
            ),
            scratch_shapes=scratch,
        ),
        out_shape=out_shape, interpret=interpret,
    )(*sched, *operands)


def _logits(mask, a, b, row0, col0, cut, keys_first=False):
    """A piece's scores ``a b^T``: queries by keys, or keys by queries
    with ``keys_first`` (the queries carry the scale); the piece's first
    query is ``row0`` and its first key row ``col0``. Where an edge of the
    mask has ``cut`` the piece, the pairs the mask forbids are at
    ``_NEG_INF``; else the scores are bare."""
    logits = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    if not cut:
        return logits
    n_queries, n_keys = logits.shape[::-1] if keys_first else logits.shape
    return jnp.where(
        mask.piece(row0, col0, n_queries, n_keys, keys_first),
        logits, _NEG_INF,
    )


def _weights(logits, rows_max, cut):
    """``exp(logits - rows_max)``, exactly 0 at the pairs masked in a
    ``cut`` piece (a row with nothing seen yet has ``rows_max`` at
    ``_NEG_INF`` too)."""
    p = jnp.exp(logits - rows_max)
    return jnp.where(logits <= _NEG_INF / 2, 0.0, p) if cut else p


def _across(stat, width: int):
    """A row statistic kept in every lane, ``[rows, _LANES]``, across
    ``width`` columns. Copies of whole lane tiles, where a ``[rows, 1]``
    column would be spread over the lanes anew at every use."""
    if width <= _LANES:
        return stat[:, :width]
    if width % _LANES:
        return stat[:, :1]
    return pltpu.repeat(stat, width // _LANES, axis=1)


# ---------------------------------------------------------------- forward


def _fwd_kernel(sched, q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s,
                *, scale, mask, block_q, block_k, t, classes):
    step = _Step(sched, classes, t)
    qi, ki = step.qi, step.ki

    @pl.when(step.first())
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    def piece(r, c, rows, cols, cut):
        """One online-softmax update of a sub-row of queries over the
        keys of its live sub-tiles."""
        logits = _logits(
            mask, q_ref[0, rows, :].astype(jnp.float32) * scale,
            k_ref[0, cols, :].astype(jnp.float32),
            qi * block_q + r * t, ki * block_k + c * t, cut,
        )
        m_prev = m_s[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = _weights(logits, _across(m_new, cols.size), cut)
        corr = jnp.exp(m_prev - m_new)
        l_s[rows, :] = l_s[rows, :] * corr + jnp.sum(
            p, axis=-1, keepdims=True
        )
        m_s[rows, :] = m_new
        pv = jax.lax.dot_general(
            p, v_ref[0, cols, :].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        acc[rows, :] = acc[rows, :] * _across(corr, acc.shape[1]) + pv

    step.pieces(piece)

    @pl.when(step.last())
    def _():
        l = l_s[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / _across(l_safe, acc.shape[1])).astype(
            o_ref.dtype
        )
        # lse blocks span the full row (TPU tiling forbids a (1, block_q)
        # block over [B*H, S]); each qi writes its slice.
        lse_ref[0, 0, pl.dslice(qi * block_q, block_q)] = (
            m_s[:, 0] + jnp.log(l_safe[:, 0])
        )


def _blocks(mask, s_q, s_k, block_q, block_k) -> tuple:
    """``(block_q, block_k, t)``: the blocks a call runs with and the side
    of the sub-tiles its kernels compute them in."""
    block_q, block_k = pick_blocks(mask, s_q, s_k, block_q, block_k)
    return block_q, block_k, sub_tile(block_q, block_k)


def _flash_fwd(q, k, v, mask, block_q, block_k, interpret):
    return _fwd_call(
        q, k, v, mask,
        _blocks(mask, q.shape[1], k.shape[1], block_q, block_k), interpret,
    )


# The calls are jitted: the layers of a stack, and a layer's forward pass
# and its recomputation, ask for the same call, and a jitted function is
# traced once for a mask and shape where a bare ``pallas_call`` is traced
# wherever it stands (a kernel body holds a piece for every strip of
# every class of block: set-up time).
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _fwd_call(q, k, v, mask, blocks, interpret):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q, block_k, t = blocks
    scale = 1.0 / np.sqrt(d)
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, sk, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, sk, d)

    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
    ]

    kernel = functools.partial(
        _fwd_kernel, scale=scale, mask=mask,
        block_q=block_q, block_k=block_k,
    )
    q_block, k_block = ((1, block_q, d), "q"), ((1, block_k, d), "k")
    o, lse = _call(
        mask, kernel, (b * h, sq, sk, block_q, block_k, t), False,
        [q_block, k_block, k_block],
        [q_block, ((1, 1, sq), "row")],
        [
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32),
        ],
        scratch, interpret, (qf, kf, vf),
    )
    return jnp.moveaxis(o.reshape(b, h, sq, d), 1, 2), lse


# ---------------------------------------------------------------- backward


def _row_of(ref, start, size):
    """The float32 values a row (lse, delta) of the ``size`` queries from
    ``start``, a whole sub-tile's first, as the ref lays them: ``[1,
    size]``."""
    return ref[0, :, pl.dslice(start, size)]


def _bwd_dq_kernel(sched, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc, *, scale, mask, block_q, block_k, t,
                   classes):
    step = _Step(sched, classes, t)
    qi, ki = step.qi, step.ki

    @pl.when(step.first())
    def _():
        acc[:] = jnp.zeros_like(acc)

    def piece(r, c, rows, cols, cut):
        row0 = pl.multiple_of(qi * block_q + r * t, t)
        k = k_ref[0, cols, :].astype(jnp.float32)
        logits = _logits(
            mask, q_ref[0, rows, :].astype(jnp.float32) * scale, k,
            row0, ki * block_k + c * t, cut,
        )
        # the sub-row's lse and delta, turned to columns and kept in
        # every lane
        lse, delta = (
            jnp.broadcast_to(_row_of(ref, row0, t).T, (t, _LANES))
            for ref in (lse_ref, delta_ref)
        )
        p = _weights(logits, _across(lse, cols.size), cut)
        dp = jax.lax.dot_general(
            do_ref[0, rows, :].astype(jnp.float32),
            v_ref[0, cols, :].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        ds = p * (dp - _across(delta, cols.size))
        acc[rows, :] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    step.pieces(piece)

    @pl.when(step.last())
    def _():
        dq_ref[0] = acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(sched, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale, mask, block_q, block_k, t, classes):
    """Keys by queries throughout: lse and delta are then rows, as their
    refs hold them, and every product is a plain ``a b`` or ``a b^T``."""
    step = _Step(sched, classes, t)
    qi, ki = step.qi, step.ki

    @pl.when(step.first())
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def piece(c, r, cols, rows, cut):
        row0 = pl.multiple_of(qi * block_q + r * t, t)
        q = q_ref[0, rows, :].astype(jnp.float32) * scale
        do = do_ref[0, rows, :].astype(jnp.float32)
        logits = _logits(
            mask, k_ref[0, cols, :].astype(jnp.float32), q,
            row0, ki * block_k + c * t, cut, keys_first=True,
        )  # [keys, queries]
        p = _weights(logits, _row_of(lse_ref, row0, rows.size), cut)
        # dv += p^T @ do
        dv_acc[cols, :] += jax.lax.dot_general(
            p, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            v_ref[0, cols, :].astype(jnp.float32), do,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        ds = p * (dp - _row_of(delta_ref, row0, rows.size))
        # dk += ds^T @ (q * scale)  — q already carries the scale
        dk_acc[cols, :] += jax.lax.dot_general(
            ds, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    step.pieces(piece)

    @pl.when(step.last())
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(mask, block_q, block_k, interpret, res, g):
    q, k = res[:2]
    return _bwd_calls(
        mask, _blocks(mask, q.shape[1], k.shape[1], block_q, block_k),
        interpret, res, g,
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _bwd_calls(mask, blocks, interpret, res, g):
    q, k, v, o, lse = res
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q, block_k, t = blocks
    scale = 1.0 / np.sqrt(d)
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, sk, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, sk, d)
    dof = jnp.moveaxis(g, 2, 1).reshape(b * h, sq, d)
    of = jnp.moveaxis(o, 2, 1).reshape(b * h, sq, d)
    # delta = rowsum(do * o): cheap elementwise — XLA fuses it fine.
    delta = jnp.sum(
        dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1
    )[:, None, :]  # [B*H, 1, S] — matches the lse layout

    dims = (b * h, sq, sk, block_q, block_k, t)
    q_block, k_block = ((1, block_q, d), "q"), ((1, block_k, d), "k")
    row = ((1, 1, sq), "row")
    ins = [q_block, k_block, k_block, q_block, row, row]
    operands = (qf, kf, vf, dof, lse, delta)
    dq = _call(
        mask,
        functools.partial(
            _bwd_dq_kernel, scale=scale, mask=mask,
            block_q=block_q, block_k=block_k,
        ),
        dims, False, ins, q_block,
        jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        [pltpu.VMEM((block_q, d), jnp.float32)], interpret, operands,
    )

    dk, dv = _call(
        mask,
        functools.partial(
            _bwd_dkv_kernel, scale=scale, mask=mask,
            block_q=block_q, block_k=block_k,
        ),
        dims, True, ins, [k_block, k_block],
        [
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        [
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret, operands,
    )

    unfold = lambda x, s: jnp.moveaxis(x.reshape(b, h, s, d), 1, 2)
    return unfold(dq, sq), unfold(dk, sk), unfold(dv, sk)


# ---------------------------------------------------------------- public


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, mask, block_q, block_k, interpret):
    o, _ = _flash_fwd(q, k, v, mask, block_q, block_k, interpret)
    return o


#: The names (``jax.ad_checkpoint.checkpoint_name``) of what the forward
#: kernel writes and the backward kernels read: the output and the rows'
#: log-sum-exp. The kernel is a ``pallas_call`` and no ``dot_general``, so
#: a remat policy keeps them only by these names (``models/stack.py``,
#: ``remat_policy``); a block that does not runs the forward kernel
#: again in its backward pass. Outside ``jax.checkpoint`` a name is an
#: identity.
RESIDUAL_NAMES = ("flash_attn_out", "flash_attn_lse")


def _flash_attention_fwd(q, k, v, mask, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, mask, block_q, block_k, interpret)
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return o, (q, k, v, o, lse)


def _flash_attention_bwd(mask, block_q, block_k, interpret, res, g):
    return _flash_bwd(mask, block_q, block_k, interpret, res, g)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def flash_attention_shard(q, k, v, causal: Optional[bool] = None,
                          block_q: int = 512, block_k: int = 512,
                          interpret: Optional[bool] = None,
                          mask: Optional[AttentionMask] = None):
    """The kernel on device-local [B, S, H, D] blocks (differentiable).

    Call it directly on one device or inside a ``shard_map`` body;
    ``interpret=None`` follows ``dlrover_tpu.ops.interpret``. ``causal``
    or ``mask`` as for :func:`reference_attention`.
    """
    if interpret is None:
        interpret = interpret_mode.use_interpret()
    return _flash_attention(
        q, k, v, _as_mask(causal, mask), block_q, block_k, interpret
    )


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


def count_pairs(mask: AttentionMask, batch_heads: int, s_q: int, s_k: int,
                block_q: int, block_k: int):
    """Raise the program's ``attn.pairs`` counter by what one attention
    call of this shape is asked for (``kind=allowed``: the pairs the mask
    allows) and what its kernels do (``kind=computed``: the pairs of the
    live sub-tiles, which are what the bodies compute). Called where the
    call is built, so once a trace, not once a step; ``seq`` keeps calls
    of different lengths apart."""
    t = _blocks(mask, s_q, s_k, block_q, block_k)[2]
    live = int(mask.tiles(s_q, s_k, t, t)[0].sum())
    tracer = get_tracer()
    tracer.count("attn.pairs", batch_heads * mask.pairs(s_q, s_k),
                 kind="allowed", seq=s_q)
    tracer.count("attn.pairs", batch_heads * live * t * t,
                 kind="computed", seq=s_q)


def count_residuals(q, saved: bool):
    """Raise the program's ``attn.residuals`` counter by the bytes of what
    the forward kernel of one call over queries ``q`` ``[B, S, H, D]``
    writes for the backward kernels (``RESIDUAL_NAMES``: the output, and
    one float32 a row of log-sum-exp). Called by a model where a remat'ed
    block builds the call, so once a trace: ``kind=saved`` where the
    block's policy keeps them, ``kind=recomputed`` where its backward
    pass runs the forward kernel again."""
    b, s, h, d = q.shape
    get_tracer().count(
        "attn.residuals", b * s * h * (d * q.dtype.itemsize + 4),
        kind="saved" if saved else "recomputed",
    )


def flash_attention(q, k, v, causal: Optional[bool] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None,
                    mesh=None, mask: Optional[AttentionMask] = None):
    """Flash attention over GLOBAL [B, S, H, D] inputs (differentiable).

    Over the ambient mesh (or ``mesh``) of more than one device the
    kernel runs under ``shard_map``; on one device it is called bare.
    ``causal`` or ``mask`` as for :func:`reference_attention`.
    """
    mask = _as_mask(causal, mask)
    count_pairs(mask, q.shape[0] * q.shape[2], q.shape[1], k.shape[1],
                block_q, block_k)
    kernel = functools.partial(
        flash_attention_shard, block_q=block_q,
        block_k=block_k, interpret=interpret, mask=mask,
    )
    mesh = mesh if mesh is not None else _ambient_mesh()
    if mesh is None or mesh.size == 1:
        return kernel(q, k, v)
    spec = _shard_spec(mesh, q.shape)
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
