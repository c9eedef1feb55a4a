"""``auto_accelerate`` — strategy selection + sharded train-step assembly.

Parity: reference ``atorch/atorch/auto/accelerate.py:619`` (analyze model →
pick/search a Strategy → apply optimization wrappers → return wrapped
model/optim/dataloader). The TPU version is leaner because XLA does the
heavy lifting: a "strategy" is just a ``ParallelSpec`` (mesh degrees) plus
rules, and "applying" it is building one jitted train step with in/out
shardings. The dry-run profiler (reference ``auto/dry_runner/``) survives as
``profile=True``: compile and time each candidate spec, keep the fastest.
"""

import dataclasses
import time
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence

from dlrover_tpu.accel.mesh import create_mesh
from dlrover_tpu.accel.sharding import logical_rules, state_shardings, unbox
from dlrover_tpu.common.log import logger

# Training-state bytes per parameter: fp32 master + adam mu/nu + bf16 grad.
_BYTES_PER_PARAM = 16
_CPU_TEST_HBM = 16e9  # what the CPU backend (tests) pretends to have: one v5e


@dataclass(frozen=True)
class ParallelSpec:
    """Mesh degrees — the Strategy object (parity: accelerate.py Strategy +
    parallel_mode, condensed). ``zero`` is not a mesh axis: it flags
    ZeRO-1 weight-update sharding of the optimizer state over the
    existing ``data`` axis (``accel/zero.py``), composable with any of
    the degrees."""

    data: int = 1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    pipe: int = 1
    zero: bool = False
    #: Per-axis collective algorithm, e.g. ``(("data", "lat"),)``: an
    #: absent axis defaults to ``"bw"`` (flat ring reduce-scatter +
    #: all-gather — full wire volume, overlappable behind backward);
    #: ``"lat"`` is the hierarchical/fused all-reduce (slow-link volume
    #: divided by the host width, fewer launches, critical-path). Chosen
    #: per axis by the measured-bandwidth search (``accel/search.py``);
    #: stored as a sorted tuple of pairs so the frozen spec stays
    #: hashable (a dict or pair-list normalizes in ``__post_init__``).
    collectives: tuple = ()

    def __post_init__(self):
        for name in ("data", "fsdp", "tensor", "seq", "expert", "pipe"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} degree must be >= 1")
        coll = self.collectives
        if isinstance(coll, dict):
            coll = coll.items()
        norm = tuple(sorted(
            (str(axis), str(strategy)) for axis, strategy in (coll or ())
        ))
        for axis, strategy in norm:
            if strategy not in ("bw", "lat"):
                raise ValueError(
                    f"unknown collective strategy {strategy!r} for axis "
                    f"{axis!r} (want 'bw' or 'lat')"
                )
        object.__setattr__(self, "collectives", norm)

    @property
    def total(self) -> int:
        return (self.data * self.fsdp * self.tensor * self.seq
                * self.expert * self.pipe)

    def axes(self):
        return [
            (name, getattr(self, name))
            for name in ("data", "fsdp", "pipe", "seq", "expert", "tensor")
            if getattr(self, name) > 1
        ]

    def rules(self, vocab_size: int = 0):
        d = dataclasses.asdict(self)
        # Algorithm choice, not a mesh degree — no logical-axis rule.
        d.pop("collectives", None)
        return logical_rules(**d, vocab_size=vocab_size)


@dataclass
class AccelerateResult:
    spec: ParallelSpec
    mesh: Any
    rules: Any
    state: Any                   # materialized, sharded train state
    shardings: Any               # pytree of NamedSharding matching state
    batch_sharding: Any
    train_step: Callable         # (state, batch) -> (state, metrics)
    init_fn: Callable            # (rng) -> sharded state (for re-init)
    search_ranking: Any = None   # [(ParallelSpec, CostEstimate)] from the
                                 # strategy search (None for explicit specs)
    module: Any = None           # the (possibly reconfigured) flax module
                                 # the step was built for


def _device_hbm(devices) -> float:
    """Memory per device, from the runtime. Only the CPU backend reports
    none; an accelerator that cannot say is an error, not 16 GB assumed."""
    dev = devices[0]
    stats = dev.memory_stats()
    if stats and "bytes_limit" in stats:
        return float(stats["bytes_limit"])
    if dev.platform != "cpu":
        raise RuntimeError(
            f"{dev.device_kind} reports no memory_stats()['bytes_limit']; "
            "the strategy search cannot size a mesh without it"
        )
    return _CPU_TEST_HBM


def _divisors_leq(n: int, cap: int) -> List[int]:
    return [d for d in range(1, min(n, cap) + 1) if n % d == 0]


def choose_spec(param_count: int, n_devices: int, hbm: float,
                allow_tensor: bool = False) -> ParallelSpec:
    """Memory-driven heuristic (parity: the reference's local strategy
    generation, ``auto/engine/planner.py`` semantics): pure DP while the
    train state fits comfortably; otherwise shard params over an fsdp axis
    just large enough; TP only on explicit opt-in (the reference calls TP
    semi-auto too, ``optimization_library.py:14``)."""
    state_bytes = param_count * _BYTES_PER_PARAM
    budget = 0.4 * hbm  # leave room for activations + workspace
    if state_bytes <= budget:
        return ParallelSpec(data=n_devices)
    need = int(state_bytes // budget) + 1
    for f in _divisors_leq(n_devices, n_devices):
        if f >= need:
            return ParallelSpec(data=n_devices // f, fsdp=f)
    return ParallelSpec(fsdp=n_devices)


def _check_spec_axes_used(spec, abstract_state):
    """Refuse degrees the model can't use: a ``pipe``/``expert`` degree
    with no parameter carrying the matching logical axis would silently
    replicate over those devices (round-2 weak #7 — phantom axes)."""
    import jax

    # Boxed leaves (nn.Partitioned / nn.LogicallyPartitioned) carry the
    # logical axis names in a `.names` tuple.
    names = set()
    for leaf in jax.tree_util.tree_leaves(
        abstract_state, is_leaf=lambda x: hasattr(x, "names")
    ):
        if hasattr(leaf, "names"):
            names.update(n for n in leaf.names if n)
    for degree, logical in (
        (spec.pipe, "stage"), (spec.expert, "expert")
    ):
        if degree > 1 and logical not in names:
            raise ValueError(
                f"ParallelSpec has {logical!r}-axis degree {degree} but no "
                f"model parameter carries the {logical!r} logical axis — "
                "those devices would be silently wasted. Configure the "
                "model for it (e.g. GPTConfig.pipeline_stages / "
                "num_experts) or drop the degree."
            )


def split_loss(out):
    """``(scalar, metrics)`` of what a loss returned: the scalar alone
    (no metrics) or ``(scalar, {name: scalar})``."""
    if isinstance(out, tuple):
        value, metrics = out
        if "loss" in metrics:
            raise ValueError("a loss's metrics may not be named 'loss'")
        return value, dict(metrics)
    return out, {}


def make_train_step(module, optimizer, loss, mesh, rules,
                    shardings, batch_sharding, donate: bool = True,
                    grad_accum: int = 1, collectives=()):
    """Assemble the jitted SPMD train step for a given strategy.

    ``grad_accum > 1`` splits the leading batch dim into that many
    microbatches and accumulates gradients over a ``lax.scan`` before the
    optimizer update — one compiled computation, activation memory of a
    single microbatch (the ElasticTrainer's world-size-change lever).

    ``collectives`` is the spec's per-axis algorithm map. With the data
    axis on the ``"bw"`` (ring) strategy and ``DLROVER_TPU_COMMS_OVERLAP``
    on, the accumulated gradient tree's *replicated* leaves are pinned
    to their final placement per leaf after the scan: GSPMD lowers one
    bucketed cross-replica reduction per leaf instead of a single fused
    all-reduce over the whole tree, so early buckets' reductions
    overlap the remaining buckets' and the per-leaf optimizer update's
    compute — only the last bucket stays exposed. Crucially the hint
    sits *after* the microbatch accumulation, where the baseline's
    reduction also runs: every gradient element still sums the same
    addends in the same order (a bucket split of an elementwise
    all-reduce touches disjoint elements), so the loss trajectory is
    bitwise that of the serialized step — ``tests/test_comms.py`` and
    the bench's comms arm assert exact equality. (Constraining the
    running sum *inside* the scan would start reductions a microbatch
    earlier but turns sum-then-reduce into reduce-then-sum, and pinning
    fsdp-sharded leaves repartitions the backward — both are real FP
    reassociations, observed non-identical at data=4/fsdp=2.)

    ``loss(module, params, batch)`` returns the scalar to differentiate
    or ``(scalar, {name: scalar})``: what the forward pass counted
    beside it (routing counters), which the step's metrics then carry
    beside ``loss`` (under ``grad_accum`` their mean over the
    microbatches). A loss object that other callers differentiate as a
    plain scalar may carry that second form as its attribute
    ``with_metrics``; the step calls it in the object's place.
    """
    import jax
    import flax.linen as nn

    from dlrover_tpu.common import env_utils

    overlap = (
        grad_accum > 1
        and dict(collectives or ()).get("data", "bw") == "bw"
        and env_utils.COMMS_OVERLAP.get()
    )

    loss = getattr(loss, "with_metrics", loss)

    def grads_of(params, batch):
        def loss_and_metrics(p):
            return split_loss(loss(module, p, batch))

        (lv, more), grads = jax.value_and_grad(
            loss_and_metrics, has_aux=True
        )(params)
        return lv, more, grads

    def step(state, batch):
        # The mesh context makes the mesh discoverable at trace time
        # (thread_resources) — ops like ring attention shard_map over it.
        with mesh, nn.logical_axis_rules(list(rules)):
            import optax

            if grad_accum > 1:
                import jax.numpy as jnp

                b = batch.shape[0]
                if b % grad_accum:
                    raise ValueError(
                        f"batch {b} not divisible by grad_accum "
                        f"{grad_accum}"
                    )
                micro = batch.reshape(
                    grad_accum, b // grad_accum, *batch.shape[1:]
                )

                def body(carry, mb):
                    loss_sum, g_sum = carry
                    lv, more, g = grads_of(state["params"], mb)
                    g_sum = jax.tree_util.tree_map(
                        lambda a, c: a + c, g_sum, g
                    )
                    return (loss_sum + lv, g_sum), more

                zero = jax.tree_util.tree_map(
                    jnp.zeros_like, state["params"]
                )
                (loss_sum, g_sum), more = jax.lax.scan(
                    body, (jnp.zeros(()), zero), micro
                )
                more = {k: jnp.mean(v) for k, v in more.items()}
                lv = loss_sum / grad_accum
                grads = jax.tree_util.tree_map(
                    lambda g: g / grad_accum, g_sum
                )
                if overlap:
                    # Bucketed DP reduction: pin each *replicated* leaf
                    # to its final placement individually so GSPMD
                    # emits one cross-replica reduction per leaf
                    # (interleavable with the next leaves' reduce + the
                    # update sweep) instead of one fused tree-wide
                    # sync. Same graph position as the baseline's
                    # reduction → bit-identical values. Sharded (fsdp/
                    # tensor) leaves are left alone: they already
                    # reduce-scatter per leaf, and forcing a layout
                    # there repartitions the backward (observed FP
                    # reassociation at data=4/fsdp=2).
                    def _pin(g, s):
                        spec = getattr(s, "spec", None)
                        replicated = spec is not None and not any(
                            p is not None for p in spec
                        )
                        if not replicated:
                            return g
                        return jax.lax.with_sharding_constraint(g, s)

                    grads = jax.tree_util.tree_map(
                        _pin, grads, shardings["params"]
                    )
            else:
                lv, more, grads = grads_of(state["params"], batch)
            fused = getattr(optimizer, "update_and_apply", None)
            if fused is not None:
                # One kernel pass produces the new params (saves the
                # separate apply_updates HBM sweep; optim/low_bit.py).
                params, opt_state = fused(
                    grads, state["opt"], state["params"]
                )
            else:
                updates, opt_state = optimizer.update(
                    grads, state["opt"], state["params"]
                )
                params = optax.apply_updates(state["params"], updates)
            new_state = {
                "params": params, "opt": opt_state,
                "step": state["step"] + 1,
            }
            return new_state, {"loss": lv, **more}

    return jax.jit(
        step,
        in_shardings=(shardings, batch_sharding),
        out_shardings=(shardings, None),
        donate_argnums=(0,) if donate else (),
    )


def transfer_state(state, shardings):
    """Move a LIVE train state onto new shardings (in-place rescale).

    ``jax.device_put`` with a sharding destination is a layout move, not
    a recompute: where the source and destination placements overlap the
    runtime routes device-to-device copies directly, and only leaves
    whose placement actually changed pay a transfer. Values are bitwise
    preserved — resharding never changes the numbers, which is what lets
    a rescale keep the loss trajectory exactly.
    """
    import jax

    return jax.tree_util.tree_map(
        lambda s, x: jax.device_put(x, s), shardings, state
    )


def _count_replicated(abstract_params, shardings):
    """Raise ``accel.replicated_params`` by the parameter leaves that no
    mesh axis shards, and by their bytes: under fsdp the vectors a layer
    that ``sharding.keep_vectors_whole`` keeps whole, and what no rule
    shards (scalars)."""
    import jax

    from dlrover_tpu.utils.tracing import get_tracer

    whole = [
        leaf for leaf, sharding in zip(
            jax.tree_util.tree_leaves(unbox(abstract_params)),
            jax.tree_util.tree_leaves(shardings),
        )
        if all(axis is None for axis in sharding.spec)
    ]
    tracer = get_tracer()
    tracer.count("accel.replicated_params", len(whole), kind="leaves")
    tracer.count(
        "accel.replicated_params",
        sum(leaf.size * leaf.dtype.itemsize for leaf in whole),
        kind="bytes",
    )


def auto_accelerate(
    module,
    optimizer,
    sample_batch,
    loss: Callable,
    spec: Any = "auto",
    devices: Optional[Sequence] = None,
    rng: Optional[Any] = None,
    profile: bool = False,
    profile_steps: int = 3,
    allow_tensor: Optional[bool] = None,
    grad_accum: int = 1,
    registry=None,
    search_top_k: int = 4,
    offload_optimizer: bool = False,
    precision: str = "bf16",
) -> AccelerateResult:
    """Analyze → choose strategy → build sharded state + train step.

    ``loss(module, params, batch) -> scalar`` or ``(scalar, {name:
    scalar})`` (``make_train_step``). ``spec`` may be a
    ``ParallelSpec``, "auto" (cost-model search over the full strategy
    space, ``accel/search.py``), or "auto" + ``profile=True`` (dry-run
    the top-K candidates and keep the fastest, parity:
    ``auto/dry_runner/dry_runner.py``). ``allow_tensor``: None (default)
    lets the search include tensor parallelism for framework models and
    excludes it for plain ones; True enables planner-driven TP for
    plain models; False forbids tensor candidates outright.
    ``offload_optimizer=True`` keeps optimizer state at rest in host
    memory (``optim/offload.py``). ``precision="int8"`` switches the
    model's MLP contractions to AQT-style quantized int8 matmuls
    (``ops/quantized.py``; the TPU analog of the reference's fp8
    training, ``amp_optimization.py:193``) — requires a model whose
    config carries ``mlp_precision``.
    """
    import jax
    import jax.numpy as jnp

    devices = list(devices if devices is not None else jax.devices())
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    n = len(devices)

    if precision not in ("bf16", "int8"):
        raise ValueError(f"precision must be 'bf16' or 'int8', got "
                         f"{precision!r}")
    if precision == "int8":
        cfg_q = getattr(module, "cfg", None)
        if cfg_q is None or not hasattr(cfg_q, "mlp_precision"):
            raise ValueError(
                "precision='int8' needs a model config with "
                "mlp_precision (GPTConfig/LlamaConfig)"
            )
        if cfg_q.mlp_precision != "int8":
            # clone() keeps any other module attributes intact
            module = module.clone(
                cfg=dataclasses.replace(cfg_q, mlp_precision="int8")
            )
            logger.info("int8 MLP precision enabled (AQT-style)")

    def build(sp: ParallelSpec, mod=None) -> AccelerateResult:
        from jax.sharding import NamedSharding, PartitionSpec as P

        mod = mod if mod is not None else module
        if sp.total > n:
            raise ValueError(f"{sp} needs {sp.total} devices, have {n}")
        from dlrover_tpu.optim.low_bit import FusedGradientTransformation

        if sp.total > 1 and isinstance(
            optimizer, FusedGradientTransformation
        ):
            raise ValueError(
                f"adam8bit cannot run under {sp}: its int8 moments are "
                "replicated and its Pallas kernel is not partitioned "
                "over a mesh, so it is a one-device optimizer. Use "
                "optax.adamw (fp32 state, sharded by fsdp) on more than "
                "one device."
            )
        mesh = create_mesh(
            sp.axes() or [("data", 1)], devices=devices[: sp.total]
        )
        rules = sp.rules(
            vocab_size=getattr(
                getattr(mod, "cfg", None), "vocab_size", 0
            ) or 0
        )

        def init_fn(r):
            variables = mod.init(r, sample_batch)
            params = variables["params"]
            return {
                "params": params,
                "opt": optimizer.init(params),
                # Strongly typed: a Python 0 is weakly typed, a restored
                # step is not, and the two trace to different programs —
                # the first step after every restore would then miss the
                # compile cache.
                "step": jnp.zeros((), jnp.int32),
            }

        abstract = jax.eval_shape(init_fn, rng)
        from dlrover_tpu.accel.registry import (
            default_registry,
            has_annotations,
        )

        if not has_annotations(abstract["params"]) and sp.total > 1:
            # Plain model (no logical-axis metadata): the registry's
            # path/shape rules make FSDP (and registered TP) work anyway.
            reg = registry
            if reg is None and (allow_tensor or sp.tensor > 1):
                # Automatic TP placement (parity: mip_tp_planner.py):
                # one abstract trace classifies every projection as
                # column-/row-parallel; no hand-written register() calls.
                from dlrover_tpu.accel.tp_planner import plan_tp

                logger.info(
                    "planning tensor-parallel placement automatically"
                )
                reg = plan_tp(mod, rng, sample_batch)
            logger.info(
                "model carries no logical axes; auto-annotating via the "
                "sharding registry"
            )
            abstract = (reg or default_registry).annotate_state(abstract)
        _check_spec_axes_used(sp, abstract)
        if sp.zero:
            # ZeRO-1: re-annotate opt-state leaves with the zero_dp axis
            # (rules already map it to "data" — sp.rules() saw zero=True).
            # Everything downstream is unchanged: the shardings computed
            # from the relabeled tree land in the jit in/out shardings
            # and GSPMD schedules the RS/AG. No optimizer wrapper.
            from dlrover_tpu.accel.zero import apply_zero

            abstract = apply_zero(abstract, sp, rules)
        shardings = state_shardings(mesh, abstract, rules)
        if sp.fsdp > 1:
            _count_replicated(abstract["params"], shardings["params"])
        opt = optimizer
        if offload_optimizer:
            from dlrover_tpu.optim.offload import (
                host_memory_kind_supported,
                normalize_shardings,
                offload,
                offload_shardings,
            )

            if host_memory_kind_supported(devices[0]):
                abstract_opt = unbox(abstract["opt"])
                dev_opt = normalize_shardings(
                    shardings["opt"], abstract_opt
                )
                host_opt = offload_shardings(dev_opt, abstract_opt)
                shardings = dict(shardings)
                shardings["opt"] = host_opt
                opt = offload(
                    optimizer, device_shardings=dev_opt,
                    host_shardings=host_opt,
                )
            else:
                logger.warning(
                    "offload_optimizer requested but this backend has "
                    "no host memory space; keeping state in HBM"
                )
        batch_axes = dict(rules)["batch"]
        batch_sharding = NamedSharding(
            mesh, P(*([batch_axes] + [None] * (sample_batch.ndim - 1)))
        )
        # Materialize in default memory, then move the offloaded leaves
        # eagerly: compiling the whole init with host-kind outputs makes
        # XLA place init ops on the host, which not every runtime can
        # execute (the train step only ever *transfers* across spaces).
        init_shardings = shardings
        post_init_put = None
        if opt is not optimizer:  # offload active
            init_shardings = dict(shardings)
            init_shardings["opt"] = dev_opt

            def post_init_put(state):
                import jax as _jax

                state = dict(state)
                state["opt"] = jax.tree_util.tree_map(
                    lambda s, x: _jax.device_put(x, s),
                    shardings["opt"], state["opt"],
                )
                return state

        materialize = jax.jit(
            lambda r: unbox(init_fn(r)), out_shardings=init_shardings
        )
        state = materialize(rng)
        if post_init_put is not None:
            state = post_init_put(state)
            _materialize_base = materialize

            def materialize(r):
                return post_init_put(_materialize_base(r))
        train_step = make_train_step(
            mod, opt, loss, mesh, rules, shardings,
            batch_sharding, grad_accum=grad_accum,
            collectives=sp.collectives,
        )
        return AccelerateResult(
            spec=sp, mesh=mesh, rules=rules, state=state,
            shardings=shardings, batch_sharding=batch_sharding,
            train_step=train_step, init_fn=materialize, module=mod,
        )

    if isinstance(spec, ParallelSpec):
        return build(spec)

    # ---- auto: cost-model search over the full strategy space ----
    import dataclasses as _dc

    import numpy as np

    from dlrover_tpu.accel.search import (
        ModelProfile,
        reconfigure_module,
        search_spec,
    )

    def count_params(mod) -> int:
        abstract = jax.eval_shape(
            lambda r: mod.init(r, sample_batch), rng
        )
        return sum(
            int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(unbox(abstract))
        )

    params = count_params(module)
    hbm = _device_hbm(devices)
    cfg = getattr(module, "cfg", None)
    if cfg is not None and _dc.is_dataclass(cfg):
        mprofile = ModelProfile.from_config(cfg, param_count=params)
        if allow_tensor is False:
            # Explicit opt-out: strip the tensor capability from the
            # search space (the default None lets the search decide —
            # that IS the auto contract for framework models).
            mprofile = _dc.replace(mprofile, num_heads=0)
    else:
        mprofile = ModelProfile.from_params(params)
        if allow_tensor:
            # Registry-annotated plain models can TP; expose it to the
            # search by advertising a head count the degrees can divide.
            mprofile = _dc.replace(mprofile, num_heads=n)

    # Exact per-candidate state bytes need the abstract tree for the
    # *reconfigured* module (pipe adds a stage axis); cache per reshape.
    _abstract_cache = {}

    def abstract_for(sp: ParallelSpec):
        mod = reconfigure_module(module, sp, sample_batch.shape[0])
        key = (sp.pipe, getattr(getattr(mod, "cfg", None), "attn_impl", None))
        if key not in _abstract_cache:
            def init_fn(r):
                variables = mod.init(r, sample_batch)
                p = variables["params"]
                return {
                    "params": p, "opt": optimizer.init(p),
                    "step": jnp.zeros((), jnp.int32),
                }

            _abstract_cache[key] = jax.eval_shape(init_fn, rng)
        return _abstract_cache[key]

    # Hierarchy awareness: when the device set spans hosts, axes whose
    # collective block crosses the host boundary are priced at DCN.
    hosts = len({getattr(d, "process_index", 0) for d in devices})
    devices_per_host = (n + hosts - 1) // hosts if hosts > 1 else 0
    ranked = search_spec(
        mprofile, n, batch_size=sample_batch.shape[0], hbm=hbm,
        abstract_fn=abstract_for, top_k=max(1, search_top_k),
        devices_per_host=devices_per_host,
    )
    chosen, chosen_est = ranked[0]
    logger.info(
        "auto_accelerate: %.1fM params on %s devices -> search chose %s",
        params / 1e6, n, chosen,
    )
    if not chosen_est.fits(hbm) and not offload_optimizer:
        # The binding constraint is memory and most of it is optimizer
        # state at rest: say so instead of letting the compile OOM
        # mutely (parity: the reference engine's strategy feedback).
        logger.warning(
            "auto_accelerate: best strategy %s needs %.1f GB/device "
            "(%.1f GB HBM); the optimizer state is %.0f%% of it — "
            "consider offload_optimizer=True and/or the 8-bit adam",
            chosen, chosen_est.total_bytes / 1e9, hbm / 1e9,
            100 * max(
                0.0, 1 - 8.0 * params / max(chosen_est.state_bytes, 1)
            ),
        )
    if not profile or len(ranked) == 1:
        result = build(
            chosen,
            reconfigure_module(module, chosen, sample_batch.shape[0]),
        )
        result.search_ranking = ranked
        return result

    best, best_time = None, float("inf")
    for cand, _est in ranked:
        try:
            result = build(cand, reconfigure_module(module, cand, sample_batch.shape[0]))
            state, batch = result.state, jax.device_put(
                sample_batch, result.batch_sharding
            )
            state, _ = result.train_step(state, batch)  # compile + warm
            jax.block_until_ready(state)
            t0 = time.perf_counter()
            for _ in range(profile_steps):
                state, _ = result.train_step(state, batch)
            jax.block_until_ready(state)
            dt = (time.perf_counter() - t0) / profile_steps
            logger.info("dry-run %s: %.1f ms/step", cand, dt * 1e3)
            if dt < best_time:
                best, best_time = cand, dt
        except Exception as e:
            logger.warning("dry-run %s failed: %s", cand, e)
    if best is None:
        best = chosen
    result = build(
        best, reconfigure_module(module, best, sample_batch.shape[0])
    )
    result.search_ranking = ranked
    return result
