"""Pipeline parallelism — GPipe + circular (interleaved) schedules as
spatial SPMD over the mesh.

Capability parity with the reference's pipeline compiler
(``atorch/atorch/modules/distributed_modules/compilers/pipe_compiler/PipelineStage.py``:
graph-split stages, P2P send/recv between ranks, 1F1B/interleaved
runtime). The TPU-first design needs none of that machinery: stages are a
*vmapped array dimension* whose logical axis (``stage``) is sharded over
the ``pipe`` mesh axis, and a schedule is a ``scan`` over ticks in which
every stage processes its current microbatch concurrently and activations
shift one stage forward via ``jnp.roll`` on the stage dim — which XLA
lowers to a ``collective-permute`` over ICI. No P2P plumbing, no per-rank
programs: one SPMD computation, differentiable end-to-end (the roll's
transpose is the reverse permute, so the backward pass is the same
pipeline run in reverse — giving 1F1B's bounded-in-flight memory
property for free under the scan's rematerialization).

Two schedules:

- :class:`Pipeline` — GPipe. ``M + P - 1`` ticks, bubble ``(P-1)/(M+P-1)``.
- :class:`CircularPipeline` — the interleaved/"virtual stages" schedule
  (Megatron-LM interleaved 1F1B's bubble cut, praxis' circular layout):
  the layer stack is split into ``C*P`` chunks and device ``p`` owns
  chunks ``p, p+P, ..., p+(C-1)P`` (strided), so each microbatch makes
  ``C`` passes around the ring. Ticks: ``C*M + P - 1`` at ``1/C`` the
  per-tick work — the drain bubble shrinks from ``(P-1)`` full-stage
  ticks to ``(P-1)`` chunk ticks, cutting the bubble fraction ~``C``×.
  Per-tick chunk selection is a per-stage dynamic index (batched
  gather) into the local ``C`` dim of the weight bank, reading only
  the selected ``1/C`` of the resident layers each tick.

Both schedules carry an auxiliary scalar (MoE load-balance loss)
alongside the activations, so expert-parallel MoE composes with pipeline
parallelism: a stage may return ``(y, aux)`` and the pipeline returns
``(outs, aux_mean)``.

The schedules are mathematically exact — outputs are identical to
running the chunks sequentially (tested).
"""

import dataclasses
from typing import Any, Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


def gpipe_ticks(num_microbatches: int, num_stages: int) -> int:
    return num_microbatches + num_stages - 1


def circular_ticks(num_microbatches: int, num_stages: int,
                   num_repeats: int) -> int:
    return num_repeats * num_microbatches + num_stages - 1


def schedule_cost(num_microbatches: int, num_stages: int,
                  num_repeats: int = 1) -> float:
    """Wall-clock of one pipeline pass in units of one *full forward*
    (all layers, one microbatch): ticks x per-tick work. Lower is
    better; the ideal (bubble-free) value is ``M / P``."""
    if num_repeats <= 1:
        return gpipe_ticks(num_microbatches, num_stages) / num_stages
    return circular_ticks(num_microbatches, num_stages, num_repeats) / (
        num_repeats * num_stages
    )


def _split_out(out):
    """Normalize a stage output to (y, aux_scalar_per_stage)."""
    if isinstance(out, tuple):
        y, aux = out
        return y, jnp.asarray(aux, jnp.float32)
    return out, None


class _StageWrap(nn.Module):
    """Adapter giving the user's stage module a stable param path
    (``.../stages/stage/...``) under the vmap."""

    make: Callable[[], nn.Module]

    @nn.compact
    def __call__(self, x):
        return self.make()(x)


class _PipeTick(nn.Module):
    """One GPipe tick: feed, compute all stages, collect, shift."""

    make_stage: Callable[[], nn.Module]
    num_microbatches: int
    carry_axes: Tuple
    overlap_collectives: bool = True

    @nn.compact
    def __call__(self, carry, t):
        state, aux_state, outs, aux_outs, xs = carry
        m = self.num_microbatches
        p = state.shape[0]

        # Feed microbatch t into stage 0 (slot 0 holds garbage rolled off
        # the last stage otherwise; it is always overwritten while fresh
        # microbatches remain).
        inp = jnp.take(xs, jnp.minimum(t, m - 1), axis=0)
        state = state.at[0].set(jnp.where(t < m, inp, state[0]))
        aux_state = aux_state.at[0].set(
            jnp.where(t < m, 0.0, aux_state[0])
        )
        state = nn.with_logical_constraint(
            state, ("stage",) + self.carry_axes
        )

        stages = nn.vmap(
            _StageWrap,
            in_axes=0,
            out_axes=0,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            metadata_params={nn.PARTITION_NAME: "stage"},
        )(self.make_stage, name="stages")
        processed, chunk_aux = _split_out(stages(state))
        if chunk_aux is None:
            aux_proc = aux_state
        else:
            aux_proc = aux_state + chunk_aux

        # The last stage finishes microbatch t-(P-1) at this tick.
        done = t - (p - 1)
        outs = jnp.where(
            done >= 0,
            lax.dynamic_update_index_in_dim(
                outs, processed[-1], jnp.maximum(done, 0), 0
            ),
            outs,
        )
        aux_outs = jnp.where(
            done >= 0,
            lax.dynamic_update_index_in_dim(
                aux_outs, aux_proc[-1], jnp.maximum(done, 0), 0
            ),
            aux_outs,
        )
        if self.overlap_collectives:
            # Pin the collected outputs to their final placement every
            # tick: the last stage's finished microbatch moves to the
            # output shard *during* the next tick's compute (one small
            # per-tick transfer), instead of one bulk relayout after the
            # scan. Placement-only — values are bit-identical with the
            # constraint off.
            outs = nn.with_logical_constraint(
                outs, (None,) + self.carry_axes
            )
        # Shift every activation one stage forward (collective-permute
        # when the stage dim is sharded over `pipe`).
        state = jnp.roll(processed, 1, axis=0)
        aux_state = jnp.roll(aux_proc, 1, axis=0)
        return (state, aux_state, outs, aux_outs, xs), None


class Pipeline(nn.Module):
    """Run ``num_stages`` copies of ``make_stage()`` as a GPipe pipeline.

    ``make_stage`` must return a fresh flax module mapping a microbatch
    ``[mb, ...]`` to the same shape (optionally ``(y, aux_scalar)`` for
    MoE stages); its parameters get a leading ``stage`` logical axis
    (map it to the ``pipe`` mesh axis via the sharding rules).
    ``carry_axes`` are the logical axes of one microbatch (e.g.
    ``("batch", "seq", "embed")``) used to keep the in-flight
    activations sharded. Returns ``y`` or ``(y, aux_mean)`` matching the
    stage's own return shape.
    """

    make_stage: Callable[[], nn.Module]
    num_stages: int
    num_microbatches: int = 0
    carry_axes: Tuple = ("batch", None, None)
    has_aux: bool = False   # stage returns (y, aux) — e.g. MoE stages
    # Constrain finished-microbatch outputs to their final placement per
    # tick so the stage-boundary transfers interleave with compute (see
    # _PipeTick). Bit-identical either way (tests/test_comms.py,
    # TestOverlapBitIdentity); off = the serialized baseline.
    overlap_collectives: bool = True

    @nn.compact
    def __call__(self, x):
        p = self.num_stages
        m = self.num_microbatches or p
        b = x.shape[0]
        if b % m != 0:
            raise ValueError(
                f"batch {b} not divisible by {m} microbatches"
            )
        mb = b // m
        xs = x.reshape(m, mb, *x.shape[1:])
        xs = nn.with_logical_constraint(xs, (None,) + self.carry_axes)

        state = jnp.zeros((p, mb) + x.shape[1:], x.dtype)
        aux_state = jnp.zeros((p,), jnp.float32)
        outs = jnp.zeros_like(xs)
        aux_outs = jnp.zeros((m,), jnp.float32)
        ticks = nn.scan(
            _PipeTick,
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=0,
            length=m + p - 1,
        )(
            self.make_stage, m, self.carry_axes,
            self.overlap_collectives, name="ticks",
        )
        (state, _, outs, aux_outs, _), _ = ticks(
            (state, aux_state, outs, aux_outs, xs),
            jnp.arange(m + p - 1),
        )
        y = outs.reshape(b, *x.shape[1:])
        if self.has_aux:
            # Each stage contributed its mean-over-own-layers; divide by
            # the stage count so the total equals the dense model's
            # mean-over-all-layers.
            return y, jnp.mean(aux_outs) / p
        return y


def _box_bank(tree, p_, c_):
    """Reshape each leaf [P*C, ...] -> [P, C, ...] and prefix the
    logical axes with ("stage", None) so the sharding rules put chunk
    banks on the ``pipe`` mesh axis (the C dim stays device-local).
    Leaves may arrive boxed (``nn.with_logical_partitioning`` inits) or
    plain; both end up LogicallyPartitioned."""
    from flax.linen.spmd import LogicallyPartitioned

    def fix(leaf):
        if isinstance(leaf, LogicallyPartitioned):
            v = leaf.unbox()
            v = v.reshape(p_, c_, *v.shape[1:])
            return dataclasses.replace(
                leaf, value=v, names=("stage", None) + tuple(leaf.names)
            )
        v = leaf.reshape(p_, c_, *leaf.shape[1:])
        return LogicallyPartitioned(
            v, names=("stage", None) + (None,) * (v.ndim - 2)
        )

    return jax.tree_util.tree_map(
        fix, tree,
        is_leaf=lambda l: isinstance(l, LogicallyPartitioned),
    )


class CircularPipeline(nn.Module):
    """Interleaved ("circular") pipeline: ``C*P`` chunks on ``P`` stages.

    Device ``p`` owns chunks ``p, p+P, ..., p+(C-1)P``; a microbatch
    travels the ring ``C`` times. Chunk ``(c, p)`` of microbatch ``m``
    runs at tick ``t = c*M + p + m`` — neighbouring chunks are one tick
    (one ``roll``) apart, and the ring-wrap edge ``(c, P-1) → (c+1, 0)``
    has latency ``D = M - P + 1`` ticks, carried by a ``D``-slot FIFO.
    Requires ``M >= P``.

    The per-tick weight for stage position ``p`` is chunk
    ``c = clip((t-p)//M, 0, C-1)``, selected from the ``[P, C, ...]``
    weight bank by a per-stage dynamic index (batched gather) — per
    tick each device reads ``1/C`` of its resident layers, so weight
    traffic per full pass is ``(C*M+P-1)/(C*(M+P-1))`` of GPipe's
    (slightly *below* 1 for C>1; measured on-chip — see the table in
    ``docs/pipeline_schedules.md``; ``tests/test_pipeline.py``
    pins per-tick FLOPs at 1/C and slice/onehot bit-exactness).
    Gradients scatter-add back into just the selected chunk.

    Parity: Megatron interleaved 1F1B / reference ``PipelineStage.py``
    virtual stages; the spatial-SPMD formulation follows the praxis
    circular schedule. Bubble: ``(P-1)`` chunk-ticks instead of GPipe's
    ``(P-1)`` full-stage ticks — a ~``C``x cut (see ``schedule_cost``).
    """

    make_stage: Callable[[], nn.Module]   # builds ONE chunk
    num_stages: int                        # P (pipe mesh degree)
    num_repeats: int                       # C (chunks per device)
    num_microbatches: int = 0              # M >= P
    carry_axes: Tuple = ("batch", None, None)
    # Chunk-selection lowering. "slice" (default) is the per-stage
    # dynamic index / gather: 1/C of the bank per tick. "onehot" is the
    # dense contraction kept ONLY as a measurement baseline — it reads
    # the entire resident bank every tick (C x the weight traffic; see
    # docs/pipeline_schedules.md for the on-chip numbers).
    chunk_select: str = "slice"
    # Same per-tick output-placement constraint as Pipeline: finished
    # microbatches migrate to the output shard tick by tick instead of
    # in one post-scan relayout. Bit-identical either way.
    overlap_collectives: bool = True

    @nn.compact
    def __call__(self, x):
        if self.chunk_select not in ("slice", "onehot"):
            raise ValueError(
                f"chunk_select must be 'slice' or 'onehot', got "
                f"{self.chunk_select!r}"
            )
        p_ = self.num_stages
        c_ = self.num_repeats
        m = self.num_microbatches or p_
        if m < p_:
            raise ValueError(
                f"circular schedule needs microbatches >= stages "
                f"(got M={m} < P={p_})"
            )
        b = x.shape[0]
        if b % m:
            raise ValueError(
                f"batch {b} not divisible by {m} microbatches"
            )
        mb = b // m
        d_ = m - p_ + 1  # ring-wrap FIFO depth
        xs = x.reshape(m, mb, *x.shape[1:])
        xs = nn.with_logical_constraint(xs, (None,) + self.carry_axes)

        template = self.make_stage()
        dummy = jnp.zeros((mb,) + x.shape[1:], x.dtype)

        def bank_init(rng):
            # Per-chunk independent init: one key per (p, c) chunk.
            keys = jax.random.split(rng, p_ * c_)
            banks = jax.vmap(
                lambda k: template.init(k, dummy)["params"]
            )(keys)
            return _box_bank(banks, p_, c_)

        bank = nn.meta.unbox(self.param("bank", bank_init))

        # Probe the chunk's return contract at trace time via eval_shape
        # (no FLOPs): MoE chunks return (y, aux).
        probe = jax.eval_shape(
            lambda w, d: template.apply({"params": w}, d),
            jax.tree_util.tree_map(lambda a: a[0, 0], bank), dummy,
        )
        has_aux = isinstance(probe, tuple)

        def apply_chunk(w, xp):
            out = template.apply({"params": w}, xp)
            y, aux = _split_out(out)
            return y, (aux if aux is not None
                       else jnp.zeros((), jnp.float32))

        iota_p = jnp.arange(p_)

        def tick(carry, t):
            state, aux_state, buf, aux_buf, outs, aux_outs = carry
            # --- feed stage 0 ---
            rel0 = t  # t - p for p=0
            m0 = jnp.mod(rel0, m)
            c0 = rel0 // m
            slot = jnp.mod(t, d_)
            fresh = jnp.take(xs, jnp.minimum(m0, m - 1), axis=0)
            wrapped = jnp.take(buf, slot, axis=0)
            aux_wrapped = jnp.take(aux_buf, slot, axis=0)
            use_fresh = c0 == 0
            active0 = rel0 < c_ * m
            inp = jnp.where(use_fresh, fresh, wrapped)
            state = state.at[0].set(jnp.where(active0, inp, state[0]))
            aux_in = jnp.where(use_fresh, 0.0, aux_wrapped)
            aux_state = aux_state.at[0].set(
                jnp.where(active0, aux_in, aux_state[0])
            )
            state = nn.with_logical_constraint(
                state, ("stage",) + self.carry_axes
            )

            # --- select chunk weights + compute all stages ---
            # Per-stage dynamic index into the local C dim: a batched
            # gather that reads ONLY the selected chunk — 1/C of the
            # resident bank per tick. (A one-hot contraction would be
            # numerically identical but touches every chunk every tick:
            # C x the HBM weight traffic, erasing the bubble win at
            # memory-bound microbatch sizes. Its transpose also writes
            # the full-bank gradient per tick; the gather's transpose is
            # a scatter-add into just the selected chunk.)
            c_per = jnp.clip((t - iota_p) // m, 0, c_ - 1)

            if self.chunk_select == "onehot":
                onehot = jax.nn.one_hot(c_per, c_, dtype=state.dtype)
                selected = jax.tree_util.tree_map(
                    lambda w: jnp.einsum(
                        "pc...,pc->p...", w, onehot.astype(w.dtype)
                    ),
                    bank,
                )
            else:
                selected = jax.tree_util.tree_map(
                    lambda w: jax.vmap(
                        lambda wp, cp: lax.dynamic_index_in_dim(
                            wp, cp, axis=0, keepdims=False
                        )
                    )(w, c_per),
                    bank,
                )
            y, chunk_aux = jax.vmap(apply_chunk)(selected, state)
            aux_y = aux_state + chunk_aux

            # --- last stage output: done, wrap, or garbage ---
            rel_last = t - (p_ - 1)
            m_last = jnp.mod(rel_last, m)
            c_last = rel_last // m
            is_done = (rel_last >= 0) & (c_last == c_ - 1)
            is_wrap = (rel_last >= 0) & (c_last < c_ - 1)
            outs = jnp.where(
                is_done,
                lax.dynamic_update_index_in_dim(
                    outs, y[-1], jnp.maximum(m_last, 0), 0
                ),
                outs,
            )
            aux_outs = jnp.where(
                is_done,
                lax.dynamic_update_index_in_dim(
                    aux_outs, aux_y[-1], jnp.maximum(m_last, 0), 0
                ),
                aux_outs,
            )
            if self.overlap_collectives:
                outs = nn.with_logical_constraint(
                    outs, (None,) + self.carry_axes
                )
            buf = jnp.where(
                is_wrap,
                lax.dynamic_update_index_in_dim(buf, y[-1], slot, 0),
                buf,
            )
            aux_buf = jnp.where(
                is_wrap,
                lax.dynamic_update_index_in_dim(
                    aux_buf, aux_y[-1], slot, 0
                ),
                aux_buf,
            )

            state = jnp.roll(y, 1, axis=0)
            aux_state = jnp.roll(aux_y, 1, axis=0)
            return (state, aux_state, buf, aux_buf, outs, aux_outs), None

        state = jnp.zeros((p_, mb) + x.shape[1:], x.dtype)
        aux_state = jnp.zeros((p_,), jnp.float32)
        buf = jnp.zeros((d_, mb) + x.shape[1:], x.dtype)
        aux_buf = jnp.zeros((d_,), jnp.float32)
        outs = jnp.zeros_like(xs)
        aux_outs = jnp.zeros((m,), jnp.float32)
        n_ticks = circular_ticks(m, p_, c_)
        (state, _, _, _, outs, aux_outs), _ = lax.scan(
            tick,
            (state, aux_state, buf, aux_buf, outs, aux_outs),
            jnp.arange(n_ticks),
        )
        y = outs.reshape(b, *x.shape[1:])
        if has_aux:
            # C*P chunks each contributed its mean-over-own-layers.
            return y, jnp.mean(aux_outs) / (p_ * c_)
        return y
