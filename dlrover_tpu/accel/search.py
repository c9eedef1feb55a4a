"""Strategy search engine — cost-model-driven ``ParallelSpec`` selection.

Parity: the reference's acceleration engine searches the strategy space by
generating candidate optimization-method combinations, scoring them, and
dry-running the survivors (``atorch/atorch/auto/engine/acceleration_engine.py:13``,
``executor.py:36``, ``sg_algo/bayes_opt_sg.py``). The TPU-first version
searches a much cleaner space — a ``ParallelSpec`` is six mesh degrees, so
the engine can *enumerate* every factorization of the device count instead
of sampling, score each with an analytic memory + roofline model, and
optionally dry-run the top-K on the real mesh (the existing
``profile=True`` path).

The cost model has two parts:

- **Memory** (feasibility): per-device *train-state* bytes are computed
  EXACTLY from the abstract boxed state — each leaf's logical axis names
  are mapped through the spec's sharding rules and its dims divided by the
  mesh-axis sizes, which is precisely what GSPMD will do. Activations,
  gradients and the fp32 loss-path logits are estimated analytically from
  the model profile (layers, d_model, ff, vocab, remat policy).
- **Time** (ranking): compute seconds from the model FLOPs at a derated
  MXU peak, a pipeline-bubble multiplier ``(M+P-1)/M``, plus per-collective
  ICI terms using the standard volume formulas (all-gather/reduce-scatter
  for FSDP, grad all-reduce for DP, activation all-reduces for TP, KV ring
  for SP, dispatch/combine all-to-all for EP) — the scaling-book recipe.

Capability gating keeps the search honest: ``tensor`` requires head/ff
divisibility, ``seq`` requires ring attention support, ``expert`` requires
an MoE model, ``pipe`` requires a model that can be re-configured into
stages. Models expose these through their config dataclass (GPTConfig /
LlamaConfig duck-typing); arbitrary flax modules degrade to the
data/fsdp-only space, which is always safe.
"""

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

from dlrover_tpu.common.log import logger

# Derate factor on peak FLOPs — CALIBRATED against measured single-chip
# step times on TPU v5e (an earlier on-chip run, to be re-measured):
# small 124M 40.6% MFU, medium 355M 43.0%, GPT-2-xl 1.5B 36.0%, LLaMA
# 1.15B 51.6%. 0.42 is their geometric mean; every preset's measured
# step time is then within +-30% of estimate().step_s, pinned by
# tests/test_search.py::TestCalibratedAgainstChip. (Remat recompute is
# inside the derate: flops_per_token counts algorithmic FLOPs only.)
_MFU_DERATE = 0.42
# ICI per-device bandwidth (bytes/s) — v5e-class 2D torus, per the public
# spec sheet ~186 GB/s aggregate; one link direction ~45 GB/s. Ranking
# constant, overridable for tests.
_ICI_BW = 9e10
_PEAK_FLOPS_DEFAULT = 197e12  # v5e bf16
# Per-collective launch/synchronization latency (seconds). The bandwidth
# terms dominate at real scale; this term is what makes fine-grained
# parallelism (a collective every layer) correctly lose to pure DP (one
# grad all-reduce) on models too small to amortize it.
_COLL_LAT = 5e-6
# Inter-host (DCN) figures: per-device bandwidth and per-collective
# latency for mesh axes whose neighbours live on different hosts.
_DCN_BW = 2.5e9
_DCN_LAT = 100e-6
# HBM bandwidth (bytes/s), v5e spec sheet. Used for the pipeline
# weight-traffic floor: each schedule tick re-reads the device's
# resident stage weights, so a pipelined step cannot run faster than
# ticks x resident-bytes / HBM — the term that stops the search from
# picking deep pipelines at memory-bound (small-batch) operating points
# where the bubble model alone looks fine. The circular schedule with
# the default "slice" chunk selection has the same per-pass weight
# traffic as GPipe (measured on-chip, docs/pipeline_schedules.md), so
# one term covers both.
_HBM_BW = 8.19e11


def _axis_links(spec, devices_per_host: int):
    """Per-axis (bandwidth_kind) map: which mesh axes cross hosts.

    Device order follows the canonical mesh layout (mesh.AXIS_ORDER,
    outermost first); an axis is host-local iff the block its
    collectives span — its own size times everything inner to it — fits
    in one host. With ``devices_per_host=0`` (single host) every axis is
    ICI.
    """
    from dlrover_tpu.accel.mesh import AXIS_ORDER

    sizes = _axis_sizes(spec)
    crossing = {}
    for i, axis in enumerate(AXIS_ORDER):
        inner = 1
        for later in AXIS_ORDER[i + 1:]:
            inner *= sizes.get(later, 1)
        span = inner * sizes.get(axis, 1)
        crossing[axis] = bool(
            devices_per_host and span > devices_per_host
        )
    return crossing


@dataclass(frozen=True)
class ModelProfile:
    """What the search needs to know about a model. Extracted from the
    model's config dataclass when it has one (``from_config``); the
    conservative fallback (``from_params``) only enables data/fsdp."""

    param_count: int
    num_layers: int = 0
    d_model: int = 0
    ff_dim: int = 0
    seq_len: int = 0
    vocab_size: int = 0
    num_heads: int = 0
    num_experts: int = 0
    moe_top_k: int = 2
    remat: bool = False
    remat_policy: str = "nothing"
    supports_ring: bool = False      # attn_impl can be switched to "ring"
    supports_pipeline: bool = False  # cfg has pipeline_stages
    mlp_int8: bool = False           # AQT int8 MLP matmuls are ACTIVE
    vocab_params: int = 0            # embed (+ untied head) params that
                                     # live outside the layer stack
    expert_ffn_params: int = 0       # expert-sharded FFN params (all
                                     # layers, all experts); 0 when dense
    dtype_bytes: int = 2             # activation dtype (bf16)
    param_dtype_bytes: int = 4       # param (and grad) dtype; bf16
                                     # models store/grad in 2 bytes but
                                     # their optimizer state still
                                     # widens to fp32 (see below)
    # Analytic train-state bytes/param, mixed-precision recipe: param +
    # grad at param dtype, fp32 adam m/v (8), plus a separate fp32
    # master copy (4) when params are not already fp32 — the dtype
    # widening ZeRO exists to shard. fp32: 4+4+8=16; bf16: 2+2+8+4=16.
    # Exact when the abstract tree is available (state_bytes_per_device).
    state_bytes_per_param: float = 16.0
    flops_per_token: float = 0.0

    @staticmethod
    def from_config(cfg, param_count: Optional[int] = None) -> "ModelProfile":
        """Duck-typed extraction from a GPTConfig/LlamaConfig-shaped
        dataclass (the framework's model families share this shape)."""
        count = param_count
        if count is None:
            count = int(cfg.param_count())
        fields = {f.name for f in dataclasses.fields(cfg)}
        # Expert-sharded FFN params: only these divide by the expert
        # degree in per-device residency. Llama's SwiGLU has three
        # bias-free projections (gate/up/down = 3*d*f); GPT's MLP is two
        # biased denses (2*d*f + f + d). The router (d*num_experts) is
        # expert-REPLICATED, so it stays out.
        n_exp = getattr(cfg, "num_experts", 0)
        d = getattr(cfg, "d_model", 0)
        f_dim = getattr(cfg, "ff_dim", 0)
        per_expert = (
            3 * d * f_dim if "num_kv_heads" in fields
            else 2 * d * f_dim + f_dim + d
        )
        expert_ffn = (
            getattr(cfg, "num_layers", 0) * n_exp * per_expert
            if n_exp > 1 else 0
        )
        import numpy as np

        pd = 4
        try:
            pdt = getattr(cfg, "param_dtype", None)
            if pdt is not None:
                pd = int(np.dtype(pdt).itemsize)
        except Exception:
            pd = 4
        # Widened-optimizer recipe (see the field comment): param + grad
        # at param dtype + fp32 m/v + fp32 master for non-fp32 params.
        sbpp = 2.0 * pd + 8.0 + (0.0 if pd == 4 else 4.0)
        return ModelProfile(
            param_count=count,
            num_layers=getattr(cfg, "num_layers", 0),
            d_model=getattr(cfg, "d_model", 0),
            ff_dim=getattr(cfg, "ff_dim", 0),
            seq_len=getattr(cfg, "max_seq_len", 0),
            vocab_size=getattr(cfg, "vocab_size", 0),
            num_heads=getattr(cfg, "num_heads", 0),
            num_experts=getattr(cfg, "num_experts", 0),
            moe_top_k=getattr(cfg, "moe_top_k", 2),
            remat=getattr(cfg, "remat", False),
            remat_policy=getattr(cfg, "remat_policy", "nothing"),
            supports_ring="attn_impl" in fields,
            supports_pipeline="pipeline_stages" in fields,
            mlp_int8=getattr(cfg, "mlp_precision", "bf16") == "int8",
            vocab_params=(
                int(cfg.vocab_param_count())
                if hasattr(cfg, "vocab_param_count")
                else getattr(cfg, "vocab_size", 0)
                * getattr(cfg, "d_model", 0)
            ),
            expert_ffn_params=expert_ffn,
            param_dtype_bytes=pd,
            state_bytes_per_param=sbpp,
            flops_per_token=(
                float(cfg.flops_per_token())
                if hasattr(cfg, "flops_per_token") else 6.0 * count
            ),
        )

    @staticmethod
    def from_params(param_count: int) -> "ModelProfile":
        return ModelProfile(param_count=param_count,
                            flops_per_token=6.0 * param_count)


@dataclass(frozen=True)
class CostEstimate:
    """Per-device memory + estimated step time for one candidate."""

    state_bytes: float       # params + opt state + step (exact when
                             # computed from the abstract tree)
    grad_bytes: float        # transient fp32 grads (peak during bwd)
    act_bytes: float         # saved activations + loss-path logits
    compute_s: float
    comm_overlap_s: float    # FSDP gathers / DP grad sync: prefetchable,
                             # XLA hides most of it behind compute
    comm_critical_s: float   # TP all-reduces, ring passes, EP all-to-all,
                             # stage transfers: on the activation critical
                             # path, largely exposed
    bubble: float            # pipeline multiplier on compute, >= 1
    hbm_s: float = 0.0       # HBM weight-traffic floor (pipeline ticks
                             # re-read resident stage weights)

    @property
    def total_bytes(self) -> float:
        return self.state_bytes + self.grad_bytes + self.act_bytes

    @property
    def comm_s(self) -> float:
        return self.comm_overlap_s + self.comm_critical_s

    @property
    def step_s(self) -> float:
        # Roofline: the pipelined compute cannot beat its weight-traffic
        # floor (hbm_s is 0 for non-pipeline specs, where the single
        # fwd+bwd weight pass is inside _MFU_DERATE).
        return (max(self.compute_s * self.bubble, self.hbm_s)
                + 0.15 * self.comm_overlap_s
                + 0.5 * self.comm_critical_s)

    def fits(self, hbm: float, headroom: float = 0.9) -> bool:
        return self.total_bytes <= hbm * headroom


def _axis_sizes(spec) -> dict:
    return {
        "data": spec.data, "fsdp": spec.fsdp, "tensor": spec.tensor,
        "seq": spec.seq, "expert": spec.expert, "pipe": spec.pipe,
    }


def state_bytes_per_device(abstract_state, spec) -> int:
    """Exact per-device train-state bytes for a candidate spec.

    Walks the abstract boxed pytree; each leaf's logical names map
    through ``spec.rules()`` to mesh axes (less the fsdp axis of a vector
    a layer, as ``state_shardings`` places it), and every sharded dim is
    ceil-divided by the product of its mesh-axis sizes — the same
    arithmetic GSPMD performs, without building a mesh or compiling.
    ``zero`` specs first re-annotate the opt subtree exactly the way
    ``build`` will, so the memory model prices the sharded slices.
    """
    import jax

    from dlrover_tpu.accel.sharding import keep_vectors_whole

    rules_seq = spec.rules()
    if getattr(spec, "zero", False) and getattr(spec, "data", 1) > 1:
        from dlrover_tpu.accel.zero import apply_zero

        abstract_state = apply_zero(
            abstract_state, spec, rules_seq, warn=False
        )
    rules = dict(rules_seq)
    sizes = _axis_sizes(spec)

    def leaf_bytes(leaf):
        names = getattr(leaf, "names", None)
        inner = getattr(leaf, "value", leaf)
        shape = getattr(inner, "shape", ())
        dtype = getattr(inner, "dtype", None)
        itemsize = dtype.itemsize if dtype is not None else 4
        axes = keep_vectors_whole(names, [
            rules.get(names[i])
            if names is not None and i < len(names) and names[i] else None
            for i in range(len(shape))
        ])
        n = 1
        for dim, mesh_axes in zip(shape, axes):
            div = 1
            if isinstance(mesh_axes, str):
                mesh_axes = (mesh_axes,)
            for ax in mesh_axes or ():
                div *= sizes.get(ax, 1)
            n *= math.ceil(dim / div)
        return n * itemsize

    total = 0
    for leaf in jax.tree_util.tree_leaves(
        abstract_state, is_leaf=lambda x: hasattr(x, "names")
    ):
        total += leaf_bytes(leaf)
    return total


def _act_floats_per_token_layer(p: ModelProfile) -> float:
    """Saved-activation floats per token per layer under the remat
    policy. Rough by design — the constant only needs to rank policies
    and scale with d_model/ff (flash attention: no [S,S] term; under
    "dots" its output is kept beside the matmuls' — qkv 3d, attention d,
    proj d, up f, down d — and its log-sum-exp, a float a head, is left
    out)."""
    d, f = max(p.d_model, 1), max(p.ff_dim, 4 * max(p.d_model, 1))
    if p.remat and p.remat_policy == "nothing":
        return 2.0 * d                    # residual-stream boundary
    if p.remat:                           # "dots": matmul outputs saved
        return 6.0 * d + f
    return 10.0 * d + 2.0 * f             # no remat: everything


def estimate(
    profile: ModelProfile,
    spec,
    batch_size: int,
    hbm: float,
    abstract_state=None,
    peak_flops: float = _PEAK_FLOPS_DEFAULT,
    ici_bw: float = _ICI_BW,
    microbatches: int = 0,
    devices_per_host: int = 0,
    dcn_bw: float = _DCN_BW,
    hbm_bw: float = _HBM_BW,
    link_profile: Optional[dict] = None,
) -> CostEstimate:
    """Analytic memory + roofline cost for one candidate spec.

    ``devices_per_host > 0`` makes the comm terms hierarchy-aware: a
    mesh axis whose collective block spans hosts (canonical layout,
    outer axes first) is priced at ``dcn_bw`` with DCN latency — the
    model that makes hierarchical placements (fsdp inside a host, dp or
    pp across) beat host-crossing gathers.

    ``link_profile`` swaps the analytic link constants for *measured*
    figures (the master LinkProfileAggregator's per-axis fold,
    ``axis -> {bw_bytes_s, lat_s, saturated}``); axes the profile has no
    measurement for (``bw_bytes_s`` null — host-local links the agent
    probe cannot see) keep the analytic constants. The spec's
    ``collectives`` map then selects per-axis *algorithm* pricing:
    ``"bw"`` (default) is the flat ring reduce-scatter+all-gather —
    maximal wire volume, overlappable behind backward; ``"lat"`` is the
    hierarchical/fused all-reduce — reduces within a host first, so the
    slow-link wire volume divides by the host width and the launch count
    halves, but the fused collective sits on the critical path. The
    ranking therefore picks ``"bw"`` exactly where measured bandwidth
    justifies paying full volume for overlap (fast/host-local axes) and
    ``"lat"`` where a thin measured link makes volume the enemy."""
    p = profile
    dp = spec.data * spec.fsdp                      # batch shards
    tokens_dev = batch_size * max(p.seq_len, 1) / (dp * spec.seq)
    dtype_b = p.dtype_bytes

    # --- memory ---
    zero_shard = (
        spec.data if getattr(spec, "zero", False) and spec.data > 1 else 1
    )
    if abstract_state is not None:
        # Exact walk (zero specs re-slice the opt subtree inside);
        # transient grads are priced at the *param* dtype — a bf16 model
        # backprops bf16 grads, not fp32 (the old 4.0 double-counted).
        state_b = float(state_bytes_per_device(abstract_state, spec))
        param_shard = spec.fsdp * spec.tensor * spec.expert * spec.pipe
        grad_b = float(p.param_dtype_bytes) * p.param_count / param_shard
    else:
        param_shard = spec.fsdp * spec.tensor * spec.expert * spec.pipe
        # Split state_bytes_per_param into the param+grad share (stays
        # with the params) and the widened optimizer share (fp32 m/v +
        # master) — only the latter divides by the zero degree.
        opt_pp = max(
            p.state_bytes_per_param - 2.0 * p.param_dtype_bytes, 0.0
        )
        state_b = (
            (p.state_bytes_per_param - opt_pp) * p.param_count / param_shard
            + opt_pp * p.param_count / (param_shard * zero_shard)
        )
        grad_b = 0.0
    layers_dev = max(p.num_layers, 1) / spec.pipe
    act_b = (
        layers_dev * _act_floats_per_token_layer(p) * tokens_dev * dtype_b
    )
    # fp32 loss path: logits + logsumexp live once, sharded over the
    # vocab axis (tensor x pipe — see logical_rules) — dominant for
    # small models, real for all.
    if p.vocab_size:
        act_b += (tokens_dev * p.vocab_size / (spec.tensor * spec.pipe)
                  * (4.0 + dtype_b))

    # --- compute ---
    flops_step = p.flops_per_token * batch_size * max(p.seq_len, 1)
    compute_s = flops_step / spec.total / (peak_flops * _MFU_DERATE)
    if spec.tensor > 1 and p.ff_dim:
        # Narrow per-shard matmuls under-fill the MXU: derate compute
        # once the sharded ff width drops below ~2k lanes. This is what
        # makes EP beat TP on MoE models (EP keeps full-width experts)
        # and keeps TP off small models.
        eff = min(1.0, max(0.1, (p.ff_dim / spec.tensor) / 2048.0))
        compute_s /= eff
    if p.mlp_int8:
        # AQT int8 MLP matmuls: measured ~0.93x on v5e via this XLA
        # build (no double-rate int8 MXU engagement; ops/quantized.py).
        # Priced as a mild penalty so the search never *prefers* a spec
        # because int8 is on; re-fit this constant when the backend
        # exposes the 2x int8 rate.
        compute_s /= 0.93
    # Microbatching amortizes the pipeline bubble; assume the runtime
    # uses up to 4*P microbatches when the per-shard batch allows
    # (reconfigure_module applies the same rule).
    m = microbatches or _pipe_microbatches(
        spec.pipe, batch_size, dp
    )
    bubble = (m + spec.pipe - 1) / m if spec.pipe > 1 else 1.0

    # --- communication (per-axis bandwidth + per-collective α) ---
    # Each term is priced at its own axis's link: ICI within a host,
    # DCN when the axis's collective block spans hosts; a measured
    # link_profile entry overrides either constant.
    crossing = _axis_links(spec, devices_per_host)

    def bw(axis):
        measured = ((link_profile or {}).get(axis) or {}).get("bw_bytes_s")
        if measured:
            return float(measured)
        return dcn_bw if crossing.get(axis) else ici_bw

    def lat(axis):
        measured = ((link_profile or {}).get(axis) or {}).get("lat_s")
        if measured:
            return float(measured)
        return _DCN_LAT if crossing.get(axis) else _COLL_LAT

    def hier(axis):
        # Host width the "lat" algorithm's intra-host reduce collapses
        # over before touching the axis's slow link; a host-local axis
        # has no second tier, so its fused all-reduce still ships full
        # volume (and "lat" can only win there on pure launch count).
        return max(2, devices_per_host) if crossing.get(axis) else 1

    def lat_volume_s(axis, vol):
        # The hierarchical algorithm's wire time: reduce+broadcast the
        # full volume inside each host at ICI speed, then move vol/h
        # over the axis's (measured or analytic) link. Both legs are
        # fused into the step boundary — critical path. Charging the
        # intra-host leg is what keeps the trade bandwidth-sensitive:
        # on a fast axis the ring's overlap discount beats the volume
        # division, on a thin measured link it cannot.
        h = hier(axis)
        t = vol / h / bw(axis)
        if h > 1:
            t += vol / ici_bw
        return t

    strat = dict(getattr(spec, "collectives", ()) or ())
    comm_ov_s = 0.0  # prefetchable: FSDP gathers, DP grad sync
    comm_cp_s = 0.0  # critical path: TP/ring/EP/stage transfers
    pbytes_tp = 2.0 * p.param_count / (spec.tensor * spec.expert * spec.pipe)
    if spec.fsdp > 1:
        # all-gather params fwd + bwd, reduce-scatter grads (bf16 wire);
        # one collective per layer per direction.
        vol = 3.0 * pbytes_tp * (spec.fsdp - 1) / spec.fsdp
        if strat.get("fsdp") == "lat":
            comm_cp_s += lat_volume_s("fsdp", vol)
            comm_cp_s += 1.5 * layers_dev * lat("fsdp")
        else:
            comm_ov_s += vol / bw("fsdp")
            comm_cp_s += 3.0 * layers_dev * lat("fsdp")
    if spec.data > 1:
        # grad all-reduce over the pure-DP axis (on the fsdp-sharded rest).
        vol = (2.0 * (pbytes_tp / spec.fsdp)
               * (spec.data - 1) / spec.data)
        if strat.get("data") == "lat":
            comm_cp_s += lat_volume_s("data", vol)
            comm_cp_s += 0.5 * lat("data")
        else:
            comm_ov_s += vol / bw("data")
            comm_cp_s += lat("data")
    if zero_shard > 1:
        # ZeRO-1 swaps the grad all-reduce for reduce-scatter + an
        # all-gather of the updated params — the same wire volume (the
        # overlap term above already covers it), but the gather sits at
        # the step boundary where the backward pass can no longer hide
        # it: price a quarter of it exposed plus one extra collective
        # launch. This keeps replicated Adam winning ties when both
        # fit; when it doesn't fit, the memory column decides.
        ag = ((pbytes_tp / spec.fsdp) * (spec.data - 1) / spec.data
              / bw("data"))
        comm_cp_s += 0.25 * ag + lat("data")
    if spec.tensor > 1:
        # Megatron semantics: 2 activation all-reduces fwd + 2 bwd per
        # layer of [tokens, d_model]; an all-reduce moves 2x the payload
        # (reduce-scatter + all-gather).
        comm_cp_s += (8.0 * layers_dev * tokens_dev * p.d_model * dtype_b
                      * (spec.tensor - 1) / spec.tensor / bw("tensor"))
        comm_cp_s += 4.0 * layers_dev * lat("tensor")
    if spec.seq > 1:
        # ring attention: each device's K and V blocks make (seq-1) hops
        # around the ring per layer (full KV visits every shard); the
        # backward ring doubles it.
        comm_cp_s += (3.0 * 2.0 * layers_dev * tokens_dev * p.d_model
                      * dtype_b * (spec.seq - 1) / bw("seq"))
        comm_cp_s += 3.0 * layers_dev * spec.seq * lat("seq")
    if spec.expert > 1:
        # dispatch + combine all-to-all, fwd + bwd, top_k routed copies.
        comm_cp_s += (4.0 * layers_dev * tokens_dev * p.d_model * dtype_b
                      * p.moe_top_k * (spec.expert - 1) / spec.expert
                      / bw("expert"))
        comm_cp_s += 4.0 * layers_dev * lat("expert")
    hbm_s = 0.0
    if spec.pipe > 1:
        # stage-boundary activation transfers: m microbatches cross each
        # boundary fwd + bwd (one permute per schedule tick each way) —
        # the tiny traffic that makes PP the right axis to place across
        # DCN.
        comm_cp_s += 2.0 * tokens_dev * p.d_model * dtype_b / bw("pipe")
        comm_cp_s += 2.0 * (m + spec.pipe - 1) * lat("pipe")
        # Weight-traffic floor: every tick each device re-reads its
        # resident stage weights (fwd scan), and the backward replay
        # reads them again plus the grad-bank read-modify-write — ~3
        # resident passes per tick over (M+P-1) ticks. A non-pipelined
        # step reads weights once fwd + twice bwd regardless of batch,
        # so the pipeline's *extra* traffic scales with the microbatch
        # count — this is what sinks deep pipelines at small batch.
        # Only the stage-bank layers re-read per tick; the vocab-side
        # params (embedding, position table, untied LM head — exact
        # count from the config's vocab_param_count, which knows about
        # head tying) run once per step outside the pipe.
        # Only the expert-sharded FFN weights divide by the expert
        # degree; attention / norms / router are expert-replicated, so
        # dividing the WHOLE stack by spec.expert undercounted the
        # floor and made deep-pipe + high-EP specs look free.
        layer_params = max(p.param_count - p.vocab_params, 0.0)
        expert_ffn = min(float(p.expert_ffn_params), layer_params)
        dense_params = layer_params - expert_ffn
        resident_b = dtype_b * (
            dense_params / (spec.pipe * spec.tensor)
            + expert_ffn / (spec.pipe * spec.tensor * spec.expert)
        )
        hbm_s = 3.0 * (m + spec.pipe - 1) * resident_b / hbm_bw

    return CostEstimate(
        state_bytes=state_b, grad_bytes=grad_b, act_bytes=act_b,
        compute_s=compute_s, comm_overlap_s=comm_ov_s,
        comm_critical_s=comm_cp_s, bubble=bubble, hbm_s=hbm_s,
    )


def _pipe_microbatches(pipe: int, batch_size: int, dp: int) -> int:
    """Microbatch count the runtime will use for a pipe degree: up to
    4*P (bubble <= (P-1)/4P) as long as each microbatch still shards
    over the dp axis and divides the global batch."""
    if pipe <= 1:
        return 1
    for k in (4, 3, 2):
        if batch_size % (k * pipe * max(dp, 1)) == 0:
            return k * pipe
    return pipe


def _factorizations(n: int, k: int):
    """All k-tuples of positive ints whose product is n."""
    if k == 1:
        yield (n,)
        return
    for d in range(1, n + 1):
        if n % d == 0:
            for rest in _factorizations(n // d, k - 1):
                yield (d,) + rest


#: Axes whose collective algorithm is a searched dimension. Only the
#: param-sync axes: TP/ring/EP traffic is activation-shaped and its
#: algorithm is fixed by the layer semantics, but the fsdp gathers and
#: the dp grad sync genuinely admit both the flat ring (full volume,
#: overlappable) and the hierarchical fused form (reduced slow-link
#: volume, critical-path).
_STRATEGY_AXES = ("data", "fsdp")


def enumerate_specs(
    profile: ModelProfile, n_devices: int, batch_size: int,
    strategies: bool = False,
) -> List[Any]:
    """Every ParallelSpec the model can legally run on n_devices.

    ``strategies=True`` widens the space with per-axis collective
    algorithm choices on :data:`_STRATEGY_AXES` (``"lat"`` variants —
    the absent entry is the default ``"bw"`` ring), at most 3 extra
    variants per spec. Off by default: without a measured link profile
    the analytic constants price every variant identically enough that
    the extra candidates are pure search cost."""
    from dlrover_tpu.accel.accelerate import ParallelSpec

    p = profile
    out = []
    for data, fsdp, tensor, seq, expert, pipe in _factorizations(
        n_devices, 6
    ):
        if tensor > 1:
            if not p.num_heads or p.num_heads % tensor:
                continue
            if p.ff_dim and p.ff_dim % tensor:
                continue
        if tensor * pipe > 1 and p.vocab_size:
            # vocab shards over tensor x pipe (logical_rules): the dim
            # must divide evenly or materialization fails. Models with
            # awkward vocabs should pad (the standard TPU practice).
            if p.vocab_size % (tensor * pipe):
                continue
        if seq > 1:
            if not p.supports_ring or not p.seq_len:
                continue
            if p.seq_len % seq:
                continue
            if p.seq_len // seq < 1024:
                continue  # ring blocks below the kernel tile size are
                          # latency-bound, never a win
            if p.num_experts:   # ring + MoE dispatch not composed yet
                continue
        if expert > 1 and (not p.num_experts or p.num_experts % expert):
            continue
        if pipe > 1:
            if not p.supports_pipeline or not p.num_layers:
                continue
            if p.num_layers % pipe:
                continue
        if batch_size % (data * fsdp):
            continue
        if pipe > 1 and (batch_size // (data * fsdp)) % pipe:
            continue            # microbatching needs divisibility
        out.append(ParallelSpec(data=data, fsdp=fsdp, tensor=tensor,
                                seq=seq, expert=expert, pipe=pipe))
    # ZeRO-1 weight-update sharding (accel/zero.py) composes with any
    # spec that has a data axis. The estimator prices its memory cut and
    # its exposed param all-gather, so a zero variant only wins when the
    # replicated optimizer state is the binding constraint.
    out += [
        dataclasses.replace(s, zero=True) for s in out if s.data > 1
    ]
    if strategies:
        variants = []
        for s in out:
            live = [a for a in _STRATEGY_AXES if getattr(s, a) > 1]
            for mask in range(1, 1 << len(live)):
                combo = tuple(
                    (axis, "lat") for i, axis in enumerate(live)
                    if mask & (1 << i)
                )
                variants.append(dataclasses.replace(s, collectives=combo))
        out += variants
    return out


def search_spec(
    profile: ModelProfile,
    n_devices: int,
    batch_size: int,
    hbm: float,
    abstract_state=None,
    peak_flops: float = _PEAK_FLOPS_DEFAULT,
    top_k: int = 4,
    prefer: Sequence[str] = (),
    abstract_fn=None,
    ici_bw: float = _ICI_BW,
    devices_per_host: int = 0,
    dcn_bw: float = _DCN_BW,
    link_profile: Optional[dict] = None,
    strategies: bool = False,
) -> List[Tuple[Any, CostEstimate]]:
    """Rank the feasible strategy space; return the top-K (spec, cost).

    ``abstract_fn(spec) -> abstract_state`` supplies the per-candidate
    boxed tree when reconfiguration changes the param layout (pipeline
    stage axes); otherwise ``abstract_state`` is used for every
    candidate. If nothing fits in HBM, returns the least-oversubscribed
    candidates (the dry-run will be the judge — XLA sometimes fits what
    the model says won't). ``prefer`` breaks near-ties toward named
    degrees (used by tests and the MoE default).
    """
    cands = enumerate_specs(
        profile, n_devices, batch_size, strategies=strategies
    )
    if not cands:
        from dlrover_tpu.accel.accelerate import ParallelSpec

        fallback = ParallelSpec(data=1)
        ab = abstract_fn(fallback) if abstract_fn else abstract_state
        return [(fallback, estimate(
            profile, fallback, batch_size, hbm, ab, peak_flops,
            ici_bw=ici_bw, devices_per_host=devices_per_host,
            dcn_bw=dcn_bw, link_profile=link_profile))]
    scored = []
    for spec in cands:
        ab = abstract_fn(spec) if abstract_fn else abstract_state
        est = estimate(profile, spec, batch_size, hbm, ab, peak_flops,
                       ici_bw=ici_bw, devices_per_host=devices_per_host,
                       dcn_bw=dcn_bw, link_profile=link_profile)
        scored.append((spec, est))
    fitting = [s for s in scored if s[1].fits(hbm)]
    if fitting:
        pool = fitting
    else:
        # Nothing fits: keep only the most-sharded end of the space so
        # ranking-by-time can't resurrect a hopeless low-memory loser.
        min_b = min(s[1].total_bytes for s in scored)
        pool = [s for s in scored if s[1].total_bytes <= 1.10 * min_b]
        logger.warning(
            "strategy search: no candidate fits %.1f GB HBM "
            "(best needs %.1f GB); dry-run will decide",
            hbm / 1e9, min_b / 1e9,
        )

    def key(item):
        spec, est = item
        t = est.step_s
        for name in prefer:
            if getattr(spec, name, 1) > 1:
                t *= 0.95
        return t

    ranked = sorted(pool, key=key)
    top = ranked[:top_k]
    for spec, est in top:
        logger.info(
            "strategy search: %s -> %.1f GB state + %.1f GB act, "
            "est %.1f ms/step (comm %.1f ms, bubble %.2f)",
            spec, est.state_bytes / 1e9, est.act_bytes / 1e9,
            est.step_s * 1e3, est.comm_s * 1e3, est.bubble,
        )
    return top


def reconfigure_module(module, spec, batch_size: int = 0):
    """Adapt a model to the chosen spec when its config dataclass exposes
    the knobs: ``seq > 1`` flips ``attn_impl`` to the ring kernel,
    ``pipe > 1`` sets ``pipeline_stages`` (+ the microbatch count the
    cost model assumed). Returns the module unchanged when it has no
    ``cfg`` or nothing needs to change."""
    cfg = getattr(module, "cfg", None)
    if cfg is None or not dataclasses.is_dataclass(cfg):
        return module
    fields = {f.name for f in dataclasses.fields(cfg)}
    changes = {}
    if spec.seq > 1 and "attn_impl" in fields and cfg.attn_impl != "ring":
        changes["attn_impl"] = "ring"
    if spec.seq == 1 and getattr(cfg, "attn_impl", None) == "ring":
        changes["attn_impl"] = "xla"
    if "pipeline_stages" in fields:
        want = spec.pipe if spec.pipe > 1 else 0
        if (cfg.pipeline_stages or 0) != want:
            changes["pipeline_stages"] = want
        if want and batch_size and "pipeline_microbatches" in fields:
            changes["pipeline_microbatches"] = _pipe_microbatches(
                spec.pipe, batch_size, spec.data * spec.fsdp
            )
    if not changes:
        return module
    new_cfg = dataclasses.replace(cfg, **changes)
    logger.info("strategy search: reconfigured model %s", changes)
    return type(module)(new_cfg)


# ---------------- elastic mesh reshape (PR-16) ----------------

#: Spec axes whose degree change forces param/optimizer bytes to move
#: (the data axis only re-partitions the batch; params are replicated
#: across it, so changing it moves nothing at rest).
_STATE_MOVING_AXES = ("fsdp", "tensor", "seq", "expert", "pipe")


def spec_from_dict(d: dict):
    """Rebuild a ``ParallelSpec`` from its ``dataclasses.asdict`` form
    (the RescalePlan wire/journal encoding). Unknown keys are dropped so
    old masters' journals replay against newer specs."""
    from dlrover_tpu.accel.accelerate import ParallelSpec

    fields = {f.name for f in dataclasses.fields(ParallelSpec)}
    return ParallelSpec(**{
        k: v for k, v in (d or {}).items() if k in fields
    })


def spec_diff(old, new) -> str:
    """Human-readable axis-by-axis diff, e.g. ``data 2->3, tensor 2->1``.

    ``old``/``new`` may be ParallelSpecs or their asdict dicts; the
    string lands in plan logs, ``RescaleInfeasible`` nacks, timeline
    evidence lines and goodput incidents, so it names only what changed
    (``unchanged`` when nothing did)."""
    if isinstance(old, dict):
        old = spec_from_dict(old)
    if isinstance(new, dict):
        new = spec_from_dict(new)
    parts = []
    for name in ("data", "fsdp", "tensor", "seq", "expert", "pipe"):
        a, b = getattr(old, name), getattr(new, name)
        if a != b:
            parts.append(f"{name} {a}->{b}")
    if old.zero != new.zero:
        parts.append(f"zero {'on->off' if old.zero else 'off->on'}")
    oc = dict(getattr(old, "collectives", ()) or ())
    nc = dict(getattr(new, "collectives", ()) or ())
    if oc != nc:
        for axis in sorted(set(oc) | set(nc)):
            a, b = oc.get(axis, "bw"), nc.get(axis, "bw")
            if a != b:
                parts.append(f"{axis}-coll {a}->{b}")
    return ", ".join(parts) if parts else "unchanged"


def spec_move_distance(old, new) -> float:
    """How much state a transition moves, as a tie-break score: one
    point per state-moving axis whose degree changes, half a point for
    a zero flip (optimizer-state relayout only). The search uses it to
    prefer, among near-equal candidates, the spec that reshards the
    least."""
    d = 0.0
    for name in _STATE_MOVING_AXES:
        if getattr(old, name) != getattr(new, name):
            d += 1.0
    if old.zero != new.zero:
        d += 0.5
    return d


def search_reshape_spec(
    profile: ModelProfile,
    n_devices: int,
    batch_size: int,
    hbm: float,
    current_spec=None,
    abstract_state=None,
    peak_flops: float = _PEAK_FLOPS_DEFAULT,
    stickiness: float = 0.05,
    ici_bw: float = _ICI_BW,
    devices_per_host: int = 0,
    dcn_bw: float = _DCN_BW,
    link_profile: Optional[dict] = None,
) -> Optional[Tuple[Any, CostEstimate]]:
    """Constrained-world search: the best spec for ≤ ``n_devices``.

    The elastic difference from :func:`search_spec`: a membership change
    rarely lands on a friendly device count (4 → 3 with 2 heads), so the
    searched spec may deliberately *idle* devices — every total
    ``m ≤ n_devices`` is enumerated and candidates compete across
    totals, with the cost model pricing the extra accumulation a
    smaller world pays (ElasWave's TP-for-accumulation trade falls out
    of the ranking, not a special case). ``stickiness`` biases the
    choice toward ``current_spec``'s layout: among candidates within
    that fraction of the best step time, the one moving the least state
    (:func:`spec_move_distance`) wins, so a transition that *can* keep
    the mesh shape does. Returns None when nothing is feasible (callers
    fall back to the DP-only plan path)."""
    if n_devices < 1:
        return None
    # A measured profile unlocks the collective-strategy dimension: only
    # with live per-axis bandwidth can the ranking tell where the "lat"
    # variant's reduced wire volume beats the ring's overlap.
    strategies = bool(link_profile)
    cands = []
    for m in range(n_devices, 0, -1):
        cands.extend(enumerate_specs(
            profile, m, batch_size, strategies=strategies
        ))
    if not cands:
        return None
    scored = []
    for spec in cands:
        est = estimate(
            profile, spec, batch_size, hbm, abstract_state, peak_flops,
            ici_bw=ici_bw, devices_per_host=devices_per_host,
            dcn_bw=dcn_bw, link_profile=link_profile,
        )
        scored.append((spec, est))
    fitting = [s for s in scored if s[1].fits(hbm)]
    pool = fitting or scored
    pool = sorted(pool, key=lambda s: s[1].step_s)
    best_t = pool[0][1].step_s
    near = [s for s in pool if s[1].step_s <= best_t * (1.0 + stickiness)]
    if current_spec is not None:
        near.sort(key=lambda s: (
            spec_move_distance(current_spec, s[0]), s[1].step_s,
        ))
    chosen, est = near[0]
    logger.info(
        "reshape search: %d candidates for <=%d devices -> %s "
        "(est %.1f ms/step, move distance %s)",
        len(cands), n_devices, chosen, est.step_s * 1e3,
        "n/a" if current_spec is None
        else spec_move_distance(current_spec, chosen),
    )
    return chosen, est
