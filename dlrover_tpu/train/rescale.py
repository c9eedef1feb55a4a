"""Worker-side in-place rescale: apply a RescalePlan without restarting.

The master's :class:`~dlrover_tpu.master.rescale.RescaleCoordinator`
answers a membership change (node death with surviving quorum, or a
joiner) with a :class:`~dlrover_tpu.common.messages.RescalePlan` instead
of invalidating the round and letting the fleet restart. This module is
the receiving end: :class:`RescaleEngine` polls for a plan covering this
node and applies it to a LIVE training loop —

1. **retune** — the host trainer re-derives its accumulation schedule
   for the new world (``host.retune(world, rank)``; see
   :func:`dlrover_tpu.common.batching.derive_accum_schedule`) and
   rebuilds the jitted train step (the recompile is the dominant
   cost; the benchmark's ``accel.rebuild_s`` times the same rebuild in
   a restarted worker).
2. **transfer** — the live train state moves onto the new result's
   shardings via :func:`dlrover_tpu.accel.accelerate.transfer_state`
   (device-to-device where placements overlap; bitwise-preserving).
   When the plan carries a *reshape* (``plan.new_spec`` differs from
   the old — the coordinator searched a better ``ParallelSpec`` for
   the surviving devices, possibly trading TP for accumulation), the
   retune rebuilds the mesh/jitted step for the NEW spec and the state
   is hydrated hybrid: every destination shard region is split by the
   shard-cover algebra (:mod:`dlrover_tpu.common.shard_cover`) into
   pieces the *surviving* live shards cover — moved device-to-device —
   and the remainder the dead members' devices held, assembled from
   the shm snapshot's block catalog
   (``engine.memory_region_reader()``). Mixing live and snapshot bytes
   is only sound at the same step, so the hybrid nacks unless the
   snapshot step matches the live state's (the preemption plane's
   blocking shm save at the fence provides exactly this).
   When there is no live state to move at all (the caller lost it),
   the engine *hydrates* everything from the newest per-step shm
   snapshot through the flash-checkpoint block catalog (cross-degree
   re-slice, ``engine.load(template)``) — gated on the snapshot being
   no more than ``DLROVER_TPU_RESCALE_MAX_SNAPSHOT_LAG`` steps behind
   the plan's step.
3. **swap** — the :class:`DevicePrefetchIterator` source is replaced so
   buffered batches sized for the old schedule are discarded, and any
   fetched-but-unacked data shards are handed back to the master for
   re-dispatch (``ShardingClient.requeue_pending``). When the local
   batch size changes and there is no ``data_factory`` to rebuild the
   stream, the plan nacks up front instead of acking a transition the
   very next step would crash.
4. **ack** — success/failure goes back via ``RescaleAck``; any failure
   nacks, which aborts the plan master-side and falls back to the
   legacy full-restart path. In-place rescale is an optimization with a
   safety net, never a new failure mode.

``host`` is anything with ``.retune(world_size, rank)`` and ``.result``
(an :class:`~dlrover_tpu.accel.accelerate.AccelerateResult`) —
:class:`~dlrover_tpu.train.elastic_trainer.ElasticTrainer` is the
canonical one.
"""

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Optional

from dlrover_tpu.chaos.injector import fault_hit
from dlrover_tpu.chaos.sites import ChaosSite
from dlrover_tpu.common import env_utils
from dlrover_tpu.common import messages as m
from dlrover_tpu.common.constants import RendezvousName
from dlrover_tpu.common.log import logger
from dlrover_tpu.observability.events import EventKind, emit


class RescaleInfeasible(RuntimeError):
    """The runtime cannot express this transition in place (e.g. the
    process set changed under a multi-process runtime, or the snapshot
    is too stale to hydrate from). Nacked to the master, which aborts
    the plan and lets the legacy restart path take over."""


@dataclass
class RescaleTransition:
    """What :meth:`RescaleEngine.apply` hands back to the training loop."""

    plan_id: int
    ok: bool
    state: Any = None            # transferred/hydrated train state
    result: Any = None           # the rebuilt AccelerateResult
    batches: Any = None          # fresh host iterable (data_factory), or None
    wall_s: float = 0.0
    source: str = ""             # "live" | "live+snapshot" | "memory" | "storage"
    requeued_shards: int = 0
    error: str = ""
    world_size: int = 0
    accum_counts: tuple = field(default_factory=tuple)
    spec: Any = None             # the ParallelSpec applied (reshape plans)
    spec_diff: str = ""          # human old->new axis diff ("" = no reshape)
    d2d_bytes: int = 0           # hydration bytes served device-to-device
    snapshot_bytes: int = 0      # hydration bytes read from the shm snapshot


class RescaleEngine:
    def __init__(
        self,
        host,
        client=None,
        node_rank: int = 0,
        rdzv_name: str = RendezvousName.TRAINING,
        checkpointer=None,
        data_factory: Optional[Callable[[Any], Iterable]] = None,
        sharding_client=None,
    ):
        self.host = host
        self.client = client
        self.node_rank = node_rank
        self.rdzv_name = rdzv_name
        self.checkpointer = checkpointer
        self.data_factory = data_factory
        self.sharding_client = sharding_client
        #: last rendezvous round this engine settled into; the poll asks
        #: for plans newer than it (workers never learn rounds any other
        #: way — the master's plan carries the authoritative number).
        self.round = 0
        self.applied_plans = 0
        self._last_poll = 0.0
        self._advertise()

    def _advertise(self):
        """Tell the master this node can apply plans in place. The
        coordinator only issues a plan when every survivor advertised —
        a deployment that never wires an engine keeps the sub-second
        full-restart path instead of stalling on an unappliable plan."""
        if self.client is None or not env_utils.RESCALE.get():
            return
        try:
            self.client.report_model_info(
                0, 0.0, extra={"rescale_capable": True}
            )
        except Exception as e:
            # Best-effort: without the advertisement the master simply
            # keeps using the restart path for this node's transitions.
            logger.debug("rescale capability advertisement failed: %s", e)

    # ---------------- polling ----------------
    def due(self) -> bool:
        """Rate-limit the per-step poll to RESCALE_POLL_INTERVAL_S."""
        if not env_utils.RESCALE.get():
            return False
        now = time.monotonic()
        if now - self._last_poll < env_utils.RESCALE_POLL_INTERVAL_S.get():
            return False
        self._last_poll = now
        return True

    def poll(self) -> Optional[m.RescalePlan]:
        """One RPC: the newest issued plan covering this node, or None."""
        if self.client is None:
            return None
        try:
            plan = self.client.get_rescale_plan(
                self.rdzv_name, self.node_rank, self.round
            )
        except Exception as e:
            logger.debug("rescale plan poll failed: %s", e)
            return None
        if plan is None or not plan.exists:
            return None
        return plan

    def maybe_rescale(self, state=None,
                      prefetch=None) -> Optional[RescaleTransition]:
        """Poll-and-apply at the configured cadence; the training loop
        calls this once per step. Returns None when there is nothing to
        do, else the applied (or failed) transition."""
        if not self.due():
            return None
        plan = self.poll()
        if plan is None:
            return None
        # The caller is a live loop being fed by an iterator sized for
        # the old schedule; apply() must nack rather than let it keep
        # yielding wrong-sized batches into the rebuilt step.
        return self.apply(plan, state=state, prefetch=prefetch,
                          has_stream=True)

    # ---------------- applying ----------------
    def _world_size(self, world) -> int:
        return sum(world.values()) or len(world)

    def _rank_in(self, plan: m.RescalePlan) -> int:
        """This node's first process rank under the new world (node
        ranks sorted, local world sizes summed below us)."""
        ranks = sorted(plan.new_world)
        if self.node_rank not in plan.new_world:
            raise RescaleInfeasible(
                f"node {self.node_rank} is not in the new world {ranks}"
            )
        below = ranks[: ranks.index(self.node_rank)]
        return sum(plan.new_world[r] for r in below)

    def _check_feasible(self, plan: m.RescalePlan):
        import jax

        if jax.process_count() > 1 and (
            set(plan.new_world) != set(plan.old_world)
        ):
            # A multi-process JAX runtime is pinned to its coordination
            # service membership; changing the process set needs the
            # restart path. Same-membership retunes (pure schedule
            # changes) are still fine in place.
            raise RescaleInfeasible(
                "process membership changed under a multi-process "
                "runtime; in-place rescale needs a single-process "
                "(logical-world) runtime — falling back to restart"
            )

    def _check_stream(self, plan: m.RescalePlan, streaming: bool):
        """A live input stream keeps yielding old-schedule-sized batches
        after the transition; when the effective local batch size
        changes it MUST be rebuilt (``data_factory``) or the plan must
        nack — acking and then failing on the very next step would turn
        a clean restart fallback into a committed transition followed by
        a crash. Hosts that do not expose ``local_batch_size`` manage
        their own data and are exempt, as are callers that drive
        ``apply`` directly without a stream."""
        if not streaming or self.data_factory is not None:
            return
        old_local = getattr(self.host, "local_batch_size", None)
        if old_local is None or not plan.accum_counts or plan.micro_batch <= 0:
            return
        rank = self._rank_in(plan)
        if rank >= len(plan.accum_counts):
            raise RescaleInfeasible(
                f"plan schedule has {len(plan.accum_counts)} ranks but "
                f"this node computes rank {rank}"
            )
        new_local = plan.accum_counts[rank] * plan.micro_batch
        if new_local != old_local:
            raise RescaleInfeasible(
                f"local batch size changes {old_local} -> {new_local} "
                "but no data_factory was provided to rebuild the input "
                "stream"
            )

    def _verify_schedule(self, plan: m.RescalePlan):
        """Master and worker derive the schedule independently; a
        mismatch means version drift and MUST nack (silently training a
        different partition would skew the global batch)."""
        sched = getattr(self.host, "schedule", None)
        if sched is not None and plan.accum_counts and (
            list(sched.counts) != list(plan.accum_counts)
        ):
            raise RescaleInfeasible(
                f"schedule drift: master planned {list(plan.accum_counts)}"
                f" but worker derived {list(sched.counts)}"
            )

    def _hydrate(self, plan: m.RescalePlan, template) -> tuple:
        """No live state: rebuild it from the newest shm snapshot via
        the block catalog — a cross-topology restore when the snapshot
        was saved under a different mesh (the template carries the NEW
        world's shardings, so restore re-slices saved blocks onto it and
        broadcast-hydrates replicas device-to-device). Returns
        (state, source)."""
        if self.checkpointer is None:
            raise RescaleInfeasible(
                "no live train state and no checkpointer to hydrate from"
            )
        from dlrover_tpu.common import ckpt_persist

        try:
            step, state = self.checkpointer.load(template)
        except (
            ckpt_persist.ZeroDegreeMismatchError,
            ckpt_persist.TopologyMismatchError,
        ) as e:
            # The saved block catalog cannot be re-sliced onto the new
            # mesh: nack with the structural reason instead of letting
            # the generic handler bury it — the master aborts the plan
            # and survivors take the legacy restart.
            raise RescaleInfeasible(
                f"snapshot cannot be re-sliced onto the new topology: {e}"
            ) from e
        if step < 0:
            raise RescaleInfeasible("no restorable snapshot to hydrate from")
        stats = getattr(self.checkpointer, "last_restore_stats", {}) or {}
        source = stats.get("source", "memory")
        max_lag = env_utils.RESCALE_MAX_SNAPSHOT_LAG.get()
        if plan.snapshot_step >= 0 and plan.snapshot_step - step > max_lag:
            raise RescaleInfeasible(
                f"snapshot step {step} is {plan.snapshot_step - step} "
                f"behind the plan's step {plan.snapshot_step} "
                f"(max lag {max_lag}); restart must re-train the gap"
            )
        return state, source

    # ---------------- mesh reshape ----------------
    def _reshape_spec(self, plan: m.RescalePlan):
        """(new ParallelSpec to rebuild under, old->new diff string).

        The spec is None — plain same-spec retune — when the plan does
        not reshape, the worker knob is off, or the host's ``retune``
        predates the ``spec`` parameter (the master planned an
        optimization this worker cannot express; the same-spec rebuild
        is still correct because the accumulation schedule is
        spec-independent). The diff survives regardless so nacks and
        events stay attributable."""
        if not plan.reshapes:
            return None, ""
        from dlrover_tpu.accel.search import spec_diff, spec_from_dict

        old_sp = spec_from_dict(plan.old_spec) if plan.old_spec else None
        new_sp = spec_from_dict(plan.new_spec)
        diff = spec_diff(old_sp, new_sp) if old_sp is not None else ""
        if not env_utils.RESCALE_RESHAPE.get():
            return None, diff
        import inspect

        try:
            params = inspect.signature(self.host.retune).parameters
        except (TypeError, ValueError):
            params = {}
        if "spec" not in params:
            logger.warning(
                "plan %s reshapes (%s) but host.retune takes no spec; "
                "rebuilding under the old spec", plan.plan_id, diff,
            )
            return None, diff
        return new_sp, diff

    def _lost_devices(self, plan: m.RescalePlan, old_result) -> list:
        """Devices whose HBM left with the dead members.

        Logical-world mapping (the only runtime in-place membership
        change supports): the old mesh's device list splits evenly into
        per-process slices, process ``p`` owning
        ``devices[p*dpm:(p+1)*dpm]``. Every process of a node absent
        from the new world is dead, and its slice must not serve as a
        d2d donor — the real transfer has nothing to read there."""
        mesh = getattr(old_result, "mesh", None)
        if mesh is None:
            return []
        devices = list(mesh.devices.flat)
        old_procs = self._world_size(plan.old_world)
        if old_procs <= 0 or len(devices) % old_procs:
            return []
        dpm = len(devices) // old_procs
        lost, offset = [], 0
        for r in sorted(plan.old_world):
            n = plan.old_world[r]
            if r not in plan.new_world:
                lost.extend(devices[offset * dpm:(offset + n) * dpm])
            offset += n
        return lost

    def _snapshot_region_reader(self, plan: m.RescalePlan, state):
        """The shm snapshot's targeted region reader, for the hybrid
        hydration's dead-member remainder. Torn-mix guard: live shards
        are at the live step, so snapshot pieces must come from that
        SAME step — a staler snapshot would splice two different
        optimizer states into one tensor, which no lag budget makes
        sound (unlike :meth:`_hydrate`, where the whole state is
        uniformly behind and the loop re-trains the gap)."""
        # `checkpointer` may be a FlashCheckpointer (engine behind the
        # `.engine` property) or a bare CheckpointEngine.
        engine = getattr(self.checkpointer, "engine", self.checkpointer)
        if engine is None or not hasattr(engine, "memory_region_reader"):
            raise RescaleInfeasible(
                "dead members' shard regions need snapshot reads but no "
                "flash checkpoint engine is attached"
            )
        snap_step, read_region = engine.memory_region_reader()
        if read_region is None:
            raise RescaleInfeasible(
                "dead members' shard regions need snapshot reads but "
                "there is no warm shm snapshot"
            )
        live_step = self._live_step(state)
        if live_step is not None and snap_step != live_step:
            raise RescaleInfeasible(
                f"snapshot step {snap_step} != live state step "
                f"{live_step}; mixing them would tear the state — "
                "fence a blocking shm save before the reshape"
            )
        if live_step is None and plan.snapshot_step >= 0 and (
            snap_step != plan.snapshot_step
        ):
            raise RescaleInfeasible(
                f"snapshot step {snap_step} != plan fence step "
                f"{plan.snapshot_step}; refusing a possibly-torn hybrid"
            )
        return read_region

    @staticmethod
    def _live_step(state):
        """Best-effort step counter of a live train state (None when the
        state shape does not expose one)."""
        try:
            import jax

            leaf = None
            if isinstance(state, dict) and "step" in state:
                leaf = state["step"]
            else:
                leaf = getattr(state, "step", None)
            if leaf is None:
                return None
            return int(jax.device_get(leaf))
        except Exception:
            return None

    def _reshape_state(self, plan: m.RescalePlan, state, old_result,
                       result) -> tuple:
        """Hydrate the live state onto the NEW spec's shardings.

        Returns ``(state, source, stats)`` with ``stats`` =
        ``{"d2d": bytes, "snapshot": bytes}``. With no dead members the
        whole move is :func:`transfer_state` (the runtime routes
        overlapping placements d2d itself). With dead members, each
        destination region is split by the shard-cover algebra and
        assembled from surviving shards (d2d) plus the shm snapshot
        (the dead members' remainder)."""
        import jax

        import numpy as np

        from dlrover_tpu.accel.accelerate import transfer_state
        from dlrover_tpu.common import shard_cover

        stats = {"d2d": 0, "snapshot": 0}
        lost = self._lost_devices(plan, old_result)
        if not lost:
            new_state = transfer_state(state, result.shardings)
            stats["d2d"] = sum(
                int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(state)
                if isinstance(leaf, (jax.Array, np.ndarray))
            )
            return new_state, "live", stats
        # Lazy: leaves fully covered by survivors never open the snapshot.
        reader_cell: list = []

        def snap(path, region):
            if not reader_cell:
                reader_cell.append(self._snapshot_region_reader(plan, state))
            return reader_cell[0](path, region)

        old_leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
        tmpl_leaves = jax.tree_util.tree_leaves(result.state)
        shard_leaves = jax.tree_util.tree_leaves(result.shardings)
        if not (len(old_leaves) == len(tmpl_leaves) == len(shard_leaves)):
            raise RescaleInfeasible(
                "rebuilt state structure does not match the live state; "
                "cannot map shard covers leaf-for-leaf"
            )
        new_leaves = []
        for (kp, old_leaf), tmpl, shd in zip(
            old_leaves, tmpl_leaves, shard_leaves
        ):
            path = jax.tree_util.keystr(kp)
            rebuilt = self._reshape_leaf(
                path, old_leaf, tmpl, lost, snap, stats
            )
            if rebuilt is None:
                # scalars / unsharded leaves: a plain placement move
                rebuilt = jax.device_put(old_leaf, shd)
            new_leaves.append(rebuilt)
        new_state = jax.tree_util.tree_unflatten(treedef, new_leaves)
        source = "live+snapshot" if stats["snapshot"] else "live"
        return new_state, source, stats

    def _reshape_leaf(self, path, old_leaf, tmpl, lost, snap, stats):
        """One leaf of the hybrid hydration, mirroring the checkpoint
        engine's broadcast-restore: each UNIQUE destination region is
        materialized once (d2d donor slices + snapshot remainder) and
        replica devices hydrate d2d from that first copy. Returns None
        when the leaf has no shard structure to split (caller falls
        back to a plain device_put)."""
        import jax

        import numpy as np

        from dlrover_tpu.common import shard_cover

        if not isinstance(old_leaf, jax.Array) or not isinstance(
            tmpl, jax.Array
        ) or getattr(tmpl, "sharding", None) is None or old_leaf.ndim == 0:
            return None
        splits = shard_cover.leaf_transfer_split(old_leaf, tmpl.sharding, lost)
        donors = shard_cover.surviving_shards(old_leaf, lost)
        if not donors and any(s.d2d for s in splits.values()):
            raise RescaleInfeasible(f"no surviving shards for {path}")
        itemsize = np.dtype(old_leaf.dtype).itemsize
        donor_regions = [
            shard_cover.normalize_index(d.index, old_leaf.shape)
            for d in donors
        ]
        donor_host: dict = {}
        first_on_device: dict = {}
        singles = []
        for sh in tmpl.addressable_shards:
            region = shard_cover.normalize_index(sh.index, tmpl.shape)
            src0 = first_on_device.get(region)
            if src0 is not None:
                singles.append(jax.device_put(src0, sh.device))
                stats["d2d"] += shard_cover.region_size(region) * itemsize
                continue
            split = splits[region]
            # Whole-region single-donor match: a true device-to-device
            # put of the donor's buffer, no host detour.
            if (
                not split.snapshot and len(split.d2d) == 1
                and split.d2d[0][0] == region
                and donor_regions[split.d2d[0][1]] == region
            ):
                arr = jax.device_put(donors[split.d2d[0][1]].data, sh.device)
                stats["d2d"] += shard_cover.region_size(region) * itemsize
                first_on_device[region] = arr
                singles.append(arr)
                continue
            host = np.empty(
                tuple(e - s for s, e in region), dtype=old_leaf.dtype
            )
            for r, si in split.d2d:
                dv = donor_host.get(si)
                if dv is None:
                    dv = donor_host[si] = np.asarray(donors[si].data)
                dregion = donor_regions[si]
                src_sl = tuple(
                    slice(s - ds, e - ds)
                    for (s, e), (ds, _) in zip(r, dregion)
                )
                dst_sl = tuple(
                    slice(s - rs, e - rs)
                    for (s, e), (rs, _) in zip(r, region)
                )
                host[dst_sl] = dv[src_sl]
                stats["d2d"] += shard_cover.region_size(r) * itemsize
            for r in split.snapshot:
                piece = snap(path, r)
                dst_sl = tuple(
                    slice(s - rs, e - rs)
                    for (s, e), (rs, _) in zip(r, region)
                )
                host[dst_sl] = piece.astype(old_leaf.dtype, copy=False)
                stats["snapshot"] += shard_cover.region_size(r) * itemsize
            arr = jax.device_put(host, sh.device)
            first_on_device[region] = arr
            singles.append(arr)
        return jax.make_array_from_single_device_arrays(
            tuple(int(d) for d in tmpl.shape), tmpl.sharding, singles
        )

    def apply(self, plan: m.RescalePlan, state=None, prefetch=None,
              has_stream: bool = False) -> RescaleTransition:
        """Apply one plan to the live loop. Never raises: failures are
        nacked (master aborts → legacy restart) and reported in the
        returned transition. ``has_stream`` marks callers whose input
        iterator is sized for the old schedule (the ``fit`` loop via
        :meth:`maybe_rescale`; passing ``prefetch`` implies it): such a
        stream must be rebuildable (``data_factory``) whenever the
        local batch size changes, else the plan nacks up front."""
        t0 = time.perf_counter()
        new_world = self._world_size(plan.new_world)
        new_spec, diff = None, ""
        try:
            new_spec, diff = self._reshape_spec(plan)
        except Exception as e:
            logger.warning("reshape spec decode failed: %s", e)
        emit(
            EventKind.RESCALE_APPLY, plan_id=plan.plan_id,
            old_world=self._world_size(plan.old_world),
            new_world=new_world, round=plan.new_round,
            **({"spec_diff": diff} if diff else {}),
        )
        try:
            chaos = fault_hit(
                ChaosSite.RESCALE_TRANSFER, detail=f"plan{plan.plan_id}"
            )
            if chaos is not None:
                if chaos.kind in ("delay", "straggle"):
                    time.sleep(chaos.delay_s)  # dtlint: disable=DT003 -- scripted chaos delay, not a poll
                elif chaos.kind in ("abort", "fail"):
                    raise RescaleInfeasible("chaos: scripted transfer abort")
            self._check_feasible(plan)
            self._check_stream(plan, has_stream or prefetch is not None)
            old_result = getattr(self.host, "result", None)
            if state is None and old_result is not None:
                state = old_result.state
            # Rebuild mesh/shardings/train step for the new world — and,
            # on a reshape plan, for the searched NEW spec. The host
            # re-inits a throwaway state (part of the recompile we are
            # timing); the live state replaces it right after.
            if new_spec is not None:
                self.host.retune(
                    new_world, rank=self._rank_in(plan), spec=new_spec
                )
            else:
                self.host.retune(new_world, rank=self._rank_in(plan))
            self._verify_schedule(plan)
            result = self.host.result
            if result is None:
                raise RescaleInfeasible(
                    "host has no prepared train step to rebuild"
                )
            hydrate_stats = {"d2d": 0, "snapshot": 0}
            if state is not None:
                state, source, hydrate_stats = self._reshape_state(
                    plan, state, old_result, result
                )
            else:
                state, source = self._hydrate(plan, result.state)
            result.state = state
            batches = None
            requeued = 0
            if self.sharding_client is not None:
                requeued = self.sharding_client.requeue_pending()
            if self.data_factory is not None:
                batches = self.data_factory(self.host)
                if prefetch is not None:
                    prefetch.swap(batches, result.batch_sharding)
            self.round = plan.new_round
            self.applied_plans += 1
            wall = time.perf_counter() - t0
            self._ack(plan, True)
            emit(
                EventKind.RESCALE_COMPLETE, plan_id=plan.plan_id,
                world=new_world, wall_s=round(wall, 3), source=source,
                requeued=requeued,
                **({
                    "spec_diff": diff,
                    "d2d_bytes": int(hydrate_stats["d2d"]),
                    "snapshot_bytes": int(hydrate_stats["snapshot"]),
                } if diff else {}),
            )
            logger.info(
                "in-place rescale applied: plan %s -> world %s "
                "(accum %s) in %.3fs, state via %s%s",
                plan.plan_id, new_world,
                list(plan.accum_counts), wall, source,
                (
                    f", reshape {diff} "
                    f"(d2d {hydrate_stats['d2d']}B, "
                    f"snapshot {hydrate_stats['snapshot']}B)"
                ) if diff else "",
            )
            return RescaleTransition(
                plan_id=plan.plan_id, ok=True, state=state, result=result,
                batches=batches, wall_s=wall, source=source,
                requeued_shards=requeued, world_size=new_world,
                accum_counts=tuple(plan.accum_counts),
                spec=getattr(result, "spec", None), spec_diff=diff,
                d2d_bytes=int(hydrate_stats["d2d"]),
                snapshot_bytes=int(hydrate_stats["snapshot"]),
            )
        except Exception as e:
            wall = time.perf_counter() - t0
            # The nack string is the master's (and the timeline's) only
            # window into WHY the optimization was declined — anchor it
            # with the plan round and the attempted spec transition so a
            # goodput report can say "reshape tensor 2->1 declined:
            # snapshot stale" instead of a bare error.
            ctx = f"plan {plan.plan_id} (round {plan.new_round}"
            ctx += f", {diff})" if diff else ")"
            err = f"{ctx}: {e}"
            logger.warning(
                "in-place rescale of %s failed; nacking so the "
                "master falls back to a full restart", err,
            )
            self._ack(plan, False, error=err)
            return RescaleTransition(
                plan_id=plan.plan_id, ok=False, wall_s=wall,
                error=err, world_size=new_world, spec_diff=diff,
            )

    def _ack(self, plan: m.RescalePlan, ok: bool, error: str = ""):
        if self.client is None:
            return
        try:
            self.client.report_rescale_ack(
                plan.plan_id, self.node_rank, ok, error=error
            )
        except Exception as e:
            # The master's apply-timeout aborts the plan if this never
            # lands; the worker keeps training on its new schedule only
            # after a successful settle, so a lost ack is safe.
            logger.warning("rescale ack for plan %s failed: %s",
                           plan.plan_id, e)
