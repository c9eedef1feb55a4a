"""Mixture-of-Experts with expert parallelism, TPU-first.

Capability parity with the reference's MoE stack
(``atorch/atorch/modules/moe/moe_layer.py:87-161``: top-k gate, alltoall
dispatch to experts over a process group, alltoall combine). The TPU-first
design is the GShard/Switch *einsum dispatch* formulation instead of
explicit alltoalls: routing builds dense dispatch/combine tensors and the
expert computation is a batched einsum over an ``expert``-sharded weight
stack — GSPMD lowers the contractions into exactly the all-to-all +
grouped-matmul schedule the reference hand-writes, and the MXU sees one
large batched matmul per projection instead of E small ones.

Everything is static-shape (capacity-factor truncation instead of
data-dependent gather), so the whole layer jits into a single XLA
computation with no host round-trips.

Two expert layers live here. The einsum path above (``compute_dispatch``,
``MoEMLP``) serves ``num_experts`` of the GPT and LLaMA configurations and
the ``expert`` mesh axis; it drops what exceeds an expert's capacity. The
held-experts path (``HeldExperts``, ``HeldExpertsMLP``; docs/held_experts.md)
is one chip's share of a layer whose experts lie on many chips: it routes
over all of them, computes the experts it holds, and drops nothing while
its pair buffer holds the pairs routed to them.

Components:
- ``compute_dispatch``: top-k routing -> combine [N,E,C] / dispatch masks
  (Switch-style position-by-cumsum, capacity-dropping, gate renorm).
- ``load_balance_loss``: Switch aux loss (E * sum(frac_routed * mean_gate)).
- ``MoEMLP``: drop-in flax replacement for the transformer FFN; returns
  ``(out, aux_loss)``. Expert weights carry the ``expert`` logical axis, so
  ``ParallelSpec(expert=K)`` shards them K-way (EP) with zero model changes.
"""

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


def compute_dispatch(gates, top_k: int, capacity: int):
    """Top-k assignment with per-expert capacity.

    gates: [N, E] router probabilities (softmax output, fp32).
    Returns (combine [N, E, C] fp32, dispatch [N, E, C] bool). Positions
    within an expert are assigned in token order via cumsum (deterministic,
    jit-friendly); tokens overflowing ``capacity`` are dropped for that
    choice. Combine weights are renormalized over the token's selected
    gates (GShard top-2 convention), so kept routes of a token sum to <= 1.
    """
    n, e = gates.shape
    remaining = gates
    base = jnp.zeros((e,), jnp.float32)  # slots already used per expert
    combine = jnp.zeros((n, e, capacity), jnp.float32)
    selected_sum = jnp.zeros((n,), jnp.float32)
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                    # [N]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)      # [N, E]
        # Position this token would take in its expert's buffer.
        pos_all = jnp.cumsum(onehot, axis=0) - onehot + base[None, :]
        pos = jnp.sum(pos_all * onehot, axis=-1)                # [N]
        keep = (pos < capacity).astype(jnp.float32)
        gate_val = jnp.sum(remaining * onehot, axis=-1)         # [N]
        pos_oh = jax.nn.one_hot(
            pos.astype(jnp.int32), capacity, dtype=jnp.float32
        )
        combine = combine + (
            (gate_val * keep)[:, None, None]
            * onehot[:, :, None]
            * pos_oh[:, None, :]
        )
        selected_sum = selected_sum + gate_val
        base = base + jnp.sum(onehot * keep[:, None], axis=0)
        remaining = remaining * (1.0 - onehot)
    denom = jnp.where(selected_sum > 0, selected_sum, 1.0)
    combine = combine / denom[:, None, None]
    dispatch = combine > 0
    return combine, dispatch


def load_balance_loss(gates, top1_onehot):
    """Switch-Transformer auxiliary loss: E * sum_e(frac_e * prob_e).

    Minimized (=1) when routing is uniform. gates [N, E] fp32,
    top1_onehot [N, E] the first-choice assignment.
    """
    e = gates.shape[-1]
    frac = jnp.mean(top1_onehot, axis=0)   # fraction routed to each expert
    prob = jnp.mean(gates, axis=0)         # mean router probability
    return e * jnp.sum(frac * prob)


def expert_capacity(n_tokens: int, n_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Static per-expert buffer size, rounded up to a multiple of 8 so the
    [E, C, D] expert batches tile the MXU/VPU lanes cleanly."""
    c = int(np.ceil(capacity_factor * top_k * n_tokens / n_experts))
    return max(8, ((c + 7) // 8) * 8)


class MoEMLP(nn.Module):
    """Expert-parallel FFN: ``[B,S,D] -> ([B,S,D], aux_loss)``.

    Expert weight stacks are [E, ...] with the ``expert`` logical axis
    first; under ``ParallelSpec(expert=K)`` each device group holds E/K
    experts and GSPMD inserts the dispatch/combine all-to-alls. With no
    ``expert`` mesh axis the same code runs replicated (pure MoE without
    EP), and numerics are identical either way.
    """

    num_experts: int
    ff_dim: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # "gelu": 2-matrix GPT-style FFN experts; "swiglu": 3-matrix
    # gate/up/down LLaMA/Mixtral-style experts (no biases).
    mlp_type: str = "gelu"

    @nn.compact
    def __call__(self, x) -> Tuple[Any, Any]:
        b, s, d = x.shape
        n, e, f = b * s, self.num_experts, self.ff_dim
        xf = x.reshape(n, d)

        router = self.param(
            "router",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("embed", "expert")
            ),
            (d, e),
            self.param_dtype,
        )
        # Routing in fp32: gate ordering must not depend on bf16 rounding.
        logits = jnp.einsum(
            "nd,de->ne", xf.astype(jnp.float32), router.astype(jnp.float32)
        )
        gates = jax.nn.softmax(logits, axis=-1)
        top1 = jax.nn.one_hot(
            jnp.argmax(gates, axis=-1), e, dtype=jnp.float32
        )
        aux = load_balance_loss(gates, top1)

        cap = expert_capacity(n, e, self.top_k, self.capacity_factor)
        combine, dispatch = compute_dispatch(gates, self.top_k, cap)

        # Dispatch: [N,E,C] x [N,D] -> [E,C,D]. Under EP the output is
        # expert-sharded; the contraction over (data-sharded) N becomes
        # the dispatch all-to-all + psum.
        expert_in = jnp.einsum(
            "nec,nd->ecd", dispatch.astype(self.dtype), xf
        )
        expert_in = nn.with_logical_constraint(
            expert_in, ("expert", None, "embed")
        )

        w_up = self.param(
            "w_up",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("expert", "embed", "mlp")
            ),
            (e, d, f),
            self.param_dtype,
        )
        b_up = self.param(
            "b_up",
            nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ("expert", "mlp")
            ),
            (e, f),
            self.param_dtype,
        )
        w_down = self.param(
            "w_down",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("expert", "mlp", "embed")
            ),
            (e, f, d),
            self.param_dtype,
        )
        b_down = self.param(
            "b_down",
            nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ("expert", "embed")
            ),
            (e, d),
            self.param_dtype,
        )

        h = jnp.einsum(
            "ecd,edf->ecf", expert_in, w_up.astype(self.dtype)
        ) + b_up[:, None, :].astype(self.dtype)
        if self.mlp_type == "swiglu":
            w_gate = self.param(
                "w_gate",
                nn.with_logical_partitioning(
                    nn.initializers.normal(0.02),
                    ("expert", "embed", "mlp"),
                ),
                (e, d, f),
                self.param_dtype,
            )
            g = jnp.einsum(
                "ecd,edf->ecf", expert_in, w_gate.astype(self.dtype)
            )
            h = nn.silu(g) * h
        else:
            h = nn.gelu(h)
        h = nn.with_logical_constraint(h, ("expert", None, "mlp"))
        out_e = jnp.einsum(
            "ecf,efd->ecd", h, w_down.astype(self.dtype)
        ) + b_down[:, None, :].astype(self.dtype)

        # Combine: weighted gather back to token order.
        out = jnp.einsum(
            "nec,ecd->nd", combine.astype(self.dtype), out_e
        )
        return out.reshape(b, s, d), aux


# ------------------------------------------------ one chip's held experts

#: The counters one held-experts layer gives, under the names the trainer
#: raises them by (``utils/tracing.SPANS``). A stack reports the mean over
#: its expert layers.
COUNTERS = (
    "moe.pairs{kind=held}", "moe.pairs{kind=buffer}",
    "moe.pairs{kind=overflowed}", "moe.load_max_over_mean",
)


@dataclasses.dataclass(frozen=True)
class HeldExperts:
    """One chip's share of a layer of routed experts (static, hashable).

    The router scores every token against all ``routed`` experts and each
    token chooses ``per_token`` of them, wherever they lie; this chip
    holds experts ``0 .. held - 1`` and adds their terms only. A
    token-expert *pair* whose expert is held takes one row of a buffer of
    ``pair_buffer`` rows, a size the job states (0: twice a row for
    every pair the tokens could make, which with its tiles never
    overflows): the gather, the grouped matmuls (forward and both
    backward products) and the scatter-add run over all of its rows
    whatever was routed, rows without a pair with weight 0, and every
    expert's rows start on a tile of the grouped matmul (``row_tile``),
    so the kernel visits the same tiles whatever was routed. The layer is dropless while the held pairs fit; pairs
    that found no row are counted (``moe.pairs{kind=overflowed}``), never
    lost in silence. ``shared_ff_dim`` > 0 adds one SwiGLU every token
    passes through, whole on every chip."""

    routed: int
    held: int
    per_token: int
    ff_dim: int
    pair_buffer: int = 0
    route_scale: float = 1.0
    shared_ff_dim: int = 0

    def __post_init__(self):
        if not 0 < self.held <= self.routed:
            raise ValueError(
                f"{self.held} experts held of {self.routed} routed"
            )
        if not 0 < self.per_token <= self.routed:
            raise ValueError(
                f"{self.per_token} experts a token of {self.routed}"
            )
        if self.pair_buffer and not self.pair_buffer >= self.held:
            raise ValueError(
                f"a pair buffer of {self.pair_buffer} rows for "
                f"{self.held} experts"
            )

    def buffer_rows(self, tokens: int) -> int:
        """The rows the layer computes for ``tokens`` tokens. Where no
        size is stated: every pair the tokens could make and as many
        again, which is room for them whatever their experts' last
        tiles leave empty (``row_tile`` is at most a ``2 * held``-th of
        the rows)."""
        return self.pair_buffer or 2 * tokens * self.per_token

    def row_tile(self, rows: int) -> int:
        """Rows of one tile of the grouped matmul over ``rows`` rows:
        the kernel's, cut to a divisor of them that leaves every held
        expert two tiles."""
        from dlrover_tpu.ops.attention import _pick_block

        return _pick_block(rows, min(
            GROUPED_MATMUL_TILES[0], max(rows // (2 * self.held), 1)
        ))

    def expected_pairs(self, tokens: int) -> float:
        """Held pairs of ``tokens`` tokens under a uniform router."""
        return tokens * self.per_token * self.held / self.routed

    def param_count(self, d_model: int) -> int:
        """Router, held experts and the shared expert of one layer."""
        return d_model * (
            self.routed + 3 * self.held * self.ff_dim
            + 3 * self.shared_ff_dim
        )

    def active_param_count(self, d_model: int) -> float:
        """The matmul parameters a token passes through on this chip:
        the router, the shared expert, and of the held experts the
        share a uniform router sends it to."""
        return d_model * (
            self.routed + 3 * self.shared_ff_dim
            + 3 * self.ff_dim * self.per_token * self.held / self.routed
        )


def router_scores(x, router):
    """``sigmoid(x W)`` in float32: tokens ``x`` ``[N, d]`` against
    ``router`` ``[d, E]``."""
    with jax.named_scope("moe.route"):
        return jax.nn.sigmoid(jnp.einsum(
            "nd,de->ne", x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ))


def route(scores, bias, per_token: int, route_scale: float):
    """Each token's choice among the experts it scored, ``scores``
    ``[N, E]`` float32: the ``per_token`` largest of ``scores + bias``
    (``bias`` ``[E]`` moves the choice only and takes no gradient); a
    chosen expert's weight is ``route_scale * s_e / (sum of the chosen s
    + 1e-20)``, the sum over all the chosen, wherever they lie. Returns
    ``(chosen [N, k] int32, weights [N, k] float32)``."""
    with jax.named_scope("moe.route"):
        _, chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias), per_token
        )
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = route_scale * picked / (
            jnp.sum(picked, axis=-1, keepdims=True) + 1e-20
        )
        return chosen, weights


def expert_loads(chosen, routed: int):
    """Pairs of each of the ``routed`` experts, ``[routed]`` float32."""
    return jnp.sum(
        chosen[..., None] == jnp.arange(routed, dtype=chosen.dtype),
        axis=(0, 1), dtype=jnp.float32,
    )


#: Rounds of ``balanced_bias``, its first step and what a round leaves of
#: it: the steps add up to 1, the width of a sigmoid's range, and end at
#: 1e-4. Fewer rounds or a faster decay leave experts without a token
#: where the scores' common part is large (PERF.md, PR 34).
BALANCE_ROUNDS, BALANCE_STEP, BALANCE_DECAY = 64, 0.1, 0.9


def balanced_bias(scores, per_token: int):
    """The choice's bias at which the tokens of ``scores`` ``[N, E]``
    load every expert alike, as nearly as whole tokens allow: from zero,
    ``BALANCE_ROUNDS`` rounds of ``b += u * sign(mean load - load)``
    (the bias update of auxiliary-loss-free balancing, Wang et al. 2024,
    arXiv 2408.15664) with a shrinking ``u``. No gradient passes."""
    scores = jax.lax.stop_gradient(scores)
    n, routed = scores.shape
    mean = n * per_token / routed

    def loads(biased):
        # Pairs an expert, from each token's ``per_token``-th largest
        # value (a maximum, masked, ``per_token`` times): on the chip a
        # quarter of the time of ``top_k`` and a count of its indices
        # (7.8 against 35.6 ms for the 64 rounds at [16384, 256]; PERF.md,
        # PR 34). The same pairs unless equal values straddle the cut.
        rest = biased
        for _ in range(per_token - 1):
            rest = jnp.where(
                rest >= jnp.max(rest, axis=-1, keepdims=True), -jnp.inf, rest
            )
        return jnp.sum(
            biased >= jnp.max(rest, axis=-1, keepdims=True),
            axis=0, dtype=jnp.float32,
        )

    def one_round(i, bias):
        return bias + BALANCE_STEP * BALANCE_DECAY ** i * jnp.sign(
            mean - loads(scores + bias)
        )

    with jax.named_scope("moe.route"):
        return jax.lax.fori_loop(
            0, BALANCE_ROUNDS, one_round, jnp.zeros((routed,), jnp.float32)
        )


def dispatch(chosen, weights, held: int, pair_buffer: int, tile: int):
    """The pair buffer of one layer, from ``chosen`` and ``weights``
    ``[N, k]``. Expert ``e``'s pairs (in token order) lie from row
    ``bounds[e]``, a multiple of ``tile``, and the rows up to
    ``bounds[e + 1]`` are its to compute: whole tiles, the last expert's
    running to the end of the buffer. A row without a pair has weight 0
    and the token of its own number. Returns ``(token [B] int32, weight
    [B] float32, bounds [held + 1] int32, held pairs, pairs that found no
    row)``."""
    with jax.named_scope("moe.dispatch"):
        n, k = chosen.shape
        expert = chosen.reshape(-1)
        key = jnp.where(expert < held, expert, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        counts = jnp.sum(
            key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :],
            axis=0, dtype=jnp.int32,
        )
        first = jnp.cumsum(counts) - counts         # in ``order``
        ends = jnp.minimum(
            jnp.cumsum(-(-counts // tile) * tile), pair_buffer
        )
        bounds = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), ends.at[-1].set(pair_buffer)]
        )
        row = jnp.arange(pair_buffer, dtype=jnp.int32)
        group = jnp.searchsorted(bounds[1:], row, side="right").astype(
            jnp.int32
        )
        rank = row - bounds[group]
        has_pair = rank < counts[group]
        pair = order[jnp.minimum(first[group] + rank, n * k - 1)]
        token = jnp.where(has_pair, pair // k, row % n)
        weight = jnp.where(has_pair, weights.reshape(-1)[pair], 0.0)
        held_pairs = jnp.sum(counts)
        return (
            token, weight, bounds, held_pairs,
            held_pairs - jnp.sum(has_pair, dtype=jnp.int32),
        )


#: The grouped matmul's tiles ``(rows, contracted, columns)``, each cut to
#: what the operands have. Of what jax 0.9.0 brings, this kernel at these
#: tiles was the fastest on a v5e at ``[8192, 3072] x [8, 3072, 3072]``
#: (a SwiGLU's three products, forward and backward: 12.5 ms against
#: ``jax.lax.ragged_dot``'s 16.0 and 7.1 at the chip's peak; PERF.md,
#: PR 34).
GROUPED_MATMUL_TILES = (512, 1024, 1024)


def grouped_matmul(lhs, rhs, sizes, tile: int):
    """``lhs [B, k] @ rhs[e] [k, n]`` for the rows of each group ``e``,
    ``sizes [E]`` rows a group in order (they add up to ``B``), in tiles
    of ``tile`` rows: the megablox Pallas kernel, differentiable in
    ``lhs`` and ``rhs`` (the backward products are the same kernel and
    its transposed twin)."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from dlrover_tpu.ops import interpret as interpret_mode

    tiles = (tile,) + tuple(
        min(t, n) for t, n in zip(GROUPED_MATMUL_TILES[1:], rhs.shape[1:])
    )
    return megablox.gmm(
        lhs, rhs, sizes, preferred_element_type=lhs.dtype, tiling=tiles,
        interpret=interpret_mode.use_interpret(),
    )


def held_experts_ffn(x, chosen, weights, w_gate, w_up, w_down,
                     experts: HeldExperts):
    """``sum over the held experts e chosen by a token of w_e *
    SwiGLU_e(x)`` for tokens ``x`` ``[N, d]``: the routed part of this
    chip's share, through the pair buffer of ``experts``. ``w_gate``,
    ``w_up`` ``[held, d, f]``, ``w_down`` ``[held, f, d]``. Returns
    ``(y [N, d], held pairs, pairs that found no row)``."""
    rows = experts.buffer_rows(x.shape[0])
    tile = experts.row_tile(rows)
    token, weight, bounds, held_pairs, overflowed = dispatch(
        chosen, weights, experts.held, rows, tile
    )
    sizes = jnp.diff(bounds)
    with jax.named_scope("moe.experts"):
        rows = x[token]                                         # [B, d]
        # The pair's weight goes in before the last product, which is
        # linear: its rows come out weighted, a row without a pair zero.
        hidden = nn.silu(grouped_matmul(rows, w_gate, sizes, tile)) * (
            grouped_matmul(rows, w_up, sizes, tile)
        ) * weight[:, None].astype(x.dtype)
        out = grouped_matmul(hidden, w_down, sizes, tile)       # [B, d]
    with jax.named_scope("moe.combine"):
        y = jnp.zeros_like(x).at[token].add(out)
    return y, held_pairs, overflowed


def routing_counters(chosen, routed: int, held_pairs, pair_buffer: int,
                     overflowed):
    """One layer's ``COUNTERS`` as float32 scalars: held pairs, buffer
    rows, held pairs that found no row, and the largest expert's pairs
    over the mean of all ``routed`` experts'."""
    values = (
        held_pairs, pair_buffer, overflowed,
        jnp.max(expert_loads(chosen, routed)) / (chosen.size / routed),
    )
    return {
        name: jnp.asarray(v, jnp.float32) for name, v in zip(COUNTERS, values)
    }


class HeldExpertsMLP(nn.Module):
    """The FFN of a layer whose experts lie on many chips, as this chip
    computes it: ``[B, S, d] -> ([B, S, d], counters)`` with ``Shared(x) +
    sum over the held chosen experts of w_e Expert_e(x)``, every expert a
    SwiGLU without biases. The result is the chip's partial sum; what the
    other chips' experts add is theirs.

    The choice's bias is no stored buffer here. In the models this layer
    is for, a rule moves a buffer while training so that the experts'
    loads stay alike; this program has no such rule and no state beside
    its parameters, so every call takes **the bias at which the loads of
    the tokens at hand balance** (``balanced_bias``): what such a rule
    holds a trained model near. At a bias of zero a randomly initialised
    stack collapses (most of a token's router input is common to all
    tokens, so they choose the same few experts), and an optimizer step
    later a bias balanced before it does again (PERF.md, PR 34)."""

    experts: HeldExperts
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    init_std: float = 0.02

    def _weights(self, name, shape, axes):
        return self.param(
            name,
            nn.with_logical_partitioning(
                nn.initializers.normal(self.init_std), axes
            ),
            shape, self.param_dtype,
        ).astype(self.dtype)

    @nn.compact
    def __call__(self, x):
        e = self.experts
        b, s, d = x.shape
        xf = x.reshape(b * s, d)
        scores = router_scores(
            xf, self._weights("router", (d, e.routed), ("embed", None))
        )
        chosen, weights = route(
            scores, balanced_bias(scores, e.per_token), e.per_token,
            e.route_scale,
        )
        up_axes, down_axes = (None, "embed", "mlp"), (None, "mlp", "embed")
        y, held_pairs, overflowed = held_experts_ffn(
            xf, chosen, weights,
            self._weights("w_gate", (e.held, d, e.ff_dim), up_axes),
            self._weights("w_up", (e.held, d, e.ff_dim), up_axes),
            self._weights("w_down", (e.held, e.ff_dim, d), down_axes),
            e,
        )
        if e.shared_ff_dim:
            with jax.named_scope("moe.shared"):
                f = e.shared_ff_dim
                gate = self._weights("shared_gate", (d, f), ("embed", "mlp"))
                up = self._weights("shared_up", (d, f), ("embed", "mlp"))
                down = self._weights("shared_down", (f, d), ("mlp", "embed"))
                y = y + (nn.silu(xf @ gate) * (xf @ up)) @ down
        counters = routing_counters(
            chosen, e.routed, held_pairs, e.buffer_rows(b * s), overflowed
        )
        return y.reshape(b, s, d), counters
