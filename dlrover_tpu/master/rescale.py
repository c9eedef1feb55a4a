"""Master-side live rescale plane: scale change without the restart tax.

Before this coordinator every membership change paid the full
kill → rendezvous → restore cycle even when most workers never failed
(the ``restart_breakdown`` of an earlier on-chip run, to be re-measured:
spawn+init+restore+recompile is pure downtime). The rescale plane instead treats a round bump with a
surviving quorum as a *transition*: the coordinator journals and issues
a :class:`~dlrover_tpu.common.messages.RescalePlan` — old world → new
world plus the derived per-rank accumulation schedule preserving the
exact global batch — and installs the new world directly into the
rendezvous manager (:meth:`absorb_world`). Survivors poll the plan when
their round goes stale, re-shard live state in place (see
``train/rescale.py``), and ack; the plan completes when every survivor
acked, or aborts (round invalidated → legacy full restart) on the first
failure or on timeout. Everything the decision depends on is journaled
as ``("rescale", payload, ts)`` records so a relaunched master neither
forgets an issued plan nor re-issues a completed one.
"""

import time
from dataclasses import asdict
from typing import Any, Callable, Dict, List, Optional, Tuple

from dlrover_tpu.chaos.injector import fault_hit
from dlrover_tpu.chaos.sites import ChaosSite
from dlrover_tpu.common import env_utils
from dlrover_tpu.common import messages as m
from dlrover_tpu.common.batching import derive_accum_schedule
from dlrover_tpu.common.constants import RendezvousName
from dlrover_tpu.common.lockdep import instrumented_lock
from dlrover_tpu.common.log import logger
from dlrover_tpu.observability.events import EventKind, emit

PLAN_ISSUED = "issued"
PLAN_COMPLETE = "complete"
PLAN_ABORTED = "aborted"


def plan_survivors(plan: m.RescalePlan) -> List[int]:
    """Ranks that live through the transition (must apply + ack)."""
    return sorted(set(plan.old_world) & set(plan.new_world))


class RescaleCoordinator:
    #: dtlint DT009: plan lifecycle state — issued plans, their ack
    #: matrices, settle deadlines and the capability roster all move
    #: together under the coordinator lock.
    GUARDED_BY = {
        "_plans": "master.rescale",
        "_acks": "master.rescale",
        "_deadlines": "master.rescale",
        "_capable": "master.rescale",
        "_spec": "master.rescale",
        "_profile": "master.rescale",
        "_hbm": "master.rescale",
        "_last_select": "master.rescale",
        # Set once at master wiring, read-only afterwards.
        "_link_profile_fn": None,
    }

    """Decides, journals and tracks in-place scale transitions.

    Wiring: the master calls :meth:`on_node_removed` from its eviction
    path (shrink) and the servicer calls :meth:`on_node_joined` when a
    new node joins an active training world (grow). Both fall back to
    returning ``None`` — which leaves the legacy stale-round/full-restart
    path in charge — whenever the transition is not safely expressible
    in place: rescale disabled, quorum lost, batch config unknown, or
    the schedule unsatisfiable.
    """

    def __init__(
        self,
        rdzv_managers: Optional[Dict[str, Any]] = None,
        state_store=None,
    ):
        self._lock = instrumented_lock("master.rescale")
        self._rdzv_managers = rdzv_managers or {}
        self._store = state_store
        self._plans: Dict[int, m.RescalePlan] = {}
        # plan_id -> node_rank -> ok
        self._acks: Dict[int, Dict[int, bool]] = {}
        self._deadlines: Dict[int, float] = {}
        self._next_plan_id = 1
        self._global_batch = 0
        self._micro_batch = 0
        self._last_step = -1
        # Node ranks that advertised a live RescaleEngine (wired into
        # their training loop). A plan is only issued when EVERY
        # survivor can actually apply it; otherwise the fleet would sit
        # out the full apply timeout training on a stale world before
        # falling back to the restart it could have taken immediately.
        self._capable: set = set()
        # Mesh-reshape inputs (journaled as ("reshape", ...) records):
        # the fleet's current ParallelSpec, its ModelProfile and the
        # per-device HBM, all as plain dicts/floats off ModelInfo.extra.
        # Without them plans stay DP-only (schedule retunes).
        self._spec: Dict[str, Any] = {}
        self._profile: Dict[str, Any] = {}
        self._hbm: float = 0.0
        # The last searched-spec selection, for introspection and so an
        # abort's evidence can name the transition it fenced.
        self._last_select: Dict[str, Any] = {}
        # Measured-link feed (LinkProfileAggregator.search_profile,
        # wired by the master; not journaled — the profile is live
        # telemetry, and a replayed plan carries the spec it chose).
        self._link_profile_fn: Optional[Any] = None

    def set_link_profile_fn(self, fn):
        """Zero-arg callable returning the aggregator's per-axis link
        profile (or None): when present, the reshape search prices
        candidates at measured bandwidth and searches the per-axis
        collective-strategy dimension."""
        self._link_profile_fn = fn

    def axis_crossing(self) -> Dict[str, bool]:
        """Which mesh axes of the fleet's current spec cross hosts —
        the aggregator's ``set_axis_links`` input. Empty until the fleet
        reports its mesh (``set_parallel_config``)."""
        with self._lock:
            spec_d = dict(self._spec)
        if not spec_d:
            return {}
        try:
            from dlrover_tpu.accel.search import _axis_links, spec_from_dict

            cur = spec_from_dict(spec_d)
            mgr = self._rdzv_managers.get(RendezvousName.TRAINING)
            hosts = len(mgr.current_world()) if mgr is not None else 0
            dph = (
                cur.total // hosts if hosts > 1 and cur.total % hosts == 0
                else 0
            )
            return _axis_links(cur, dph)
        except Exception:
            logger.debug("axis crossing derivation failed", exc_info=True)
            return {}

    # ---------------- journal plumbing ----------------
    @property
    def _replaying(self) -> bool:
        return self._store is not None and self._store.replaying

    def _journal(self, payload: Dict[str, Any]):
        if self._store is not None and not self._store.replaying:
            self._store.append(("rescale", payload, time.time()))

    def _journal_reshape(self, payload: Dict[str, Any]):
        if self._store is not None and not self._store.replaying:
            self._store.append(("reshape", payload, time.time()))

    # ---------------- live inputs ----------------
    def set_batch_config(self, global_batch: int, micro_batch: int):
        """Record the fleet's batch contract (journaled): without it no
        accumulation schedule can be derived and every membership change
        falls back to a full restart."""
        with self._lock:
            if (
                self._global_batch == global_batch
                and self._micro_batch == micro_batch
            ):
                return
            self._global_batch = int(global_batch)
            self._micro_batch = int(micro_batch)
        self._journal({
            "rec": "config",
            "global_batch": int(global_batch),
            "micro_batch": int(micro_batch),
        })

    def set_capable(self, node_rank: int):
        """Record that a node's worker runs a live RescaleEngine
        (journaled). The engine advertises on construction via
        ``ModelInfo.extra["rescale_capable"]``; without the flag from
        every survivor the coordinator declines to plan in place."""
        with self._lock:
            if node_rank in self._capable:
                return
            self._capable.add(node_rank)
        self._journal({"rec": "capable", "node": int(node_rank)})

    def set_parallel_config(
        self, spec: Dict[str, Any], profile: Dict[str, Any],
        hbm: float = 0.0,
    ):
        """Record the fleet's mesh layout + model profile (journaled as
        a ``("reshape", ...)`` record): the inputs the constrained-world
        spec search needs. Without them a membership change can only
        retune the accumulation schedule — any job running TP/FSDP/pipe
        degrees would nack the plan and pay the restart tax."""
        spec = dict(spec or {})
        profile = dict(profile or {})
        with self._lock:
            if (
                self._spec == spec and self._profile == profile
                and (hbm <= 0 or self._hbm == hbm)
            ):
                return
            self._spec = spec
            self._profile = profile
            if hbm > 0:
                self._hbm = float(hbm)
        self._journal_reshape({
            "rec": "config", "spec": spec, "profile": profile,
            "hbm": float(hbm),
        })

    def note_step(self, step: int):
        """Track the newest reported global step — the plan's
        ``snapshot_step`` freshness fence (per-step shm snapshots mean
        the newest snapshot is at most one step behind it)."""
        with self._lock:
            self._last_step = max(self._last_step, int(step))

    # ---------------- transition triggers ----------------
    def on_node_removed(
        self,
        node_rank: int,
        old_world: Dict[int, int],
        rdzv_name: str = RendezvousName.TRAINING,
    ) -> Optional[m.RescalePlan]:
        """Shrink path: a member of the active world died/was evicted.

        Called after the rendezvous managers dropped the node (the old
        round is already stale). Returns the issued plan, or ``None``
        to leave the full-restart fallback in charge.
        """
        if self._replaying or not env_utils.RESCALE.get():
            return None
        if node_rank not in old_world:
            return None
        survivors = {
            r: w for r, w in old_world.items() if r != node_rank
        }
        if not survivors:
            return None
        quorum = env_utils.RESCALE_MIN_QUORUM.get()  # dtlint: disable=DT011 -- operator policy deliberately read live; the authoritative plan/abort state replays from ("rescale", ...) records, which overwrite any transient re-derivation
        if len(survivors) / len(old_world) < quorum:
            logger.info(
                "rescale: %d/%d survivors below quorum %.2f; falling "
                "back to full restart", len(survivors), len(old_world),
                quorum,
            )
            return None
        return self._issue_plan(
            rdzv_name, old_world, survivors, transition="shrink"
        )

    def can_plan_shrink(
        self, node_rank: int, old_world: Dict[int, int]
    ) -> Tuple[bool, str]:
        """Pre-flight for the remediation policy: would
        :meth:`on_node_removed` issue a plan for this shrink right now?

        Runs the same gates (rescale enabled, membership, survivor
        quorum, batch config, survivor capability, schedule
        satisfiability) without touching the rendezvous or issuing
        anything. The policy must know BEFORE dropping the node — an
        issued-then-declined shrink falls back to the full restart the
        quarantine exists to avoid. Returns ``(ok, reason)``.
        """
        if self._replaying or not env_utils.RESCALE.get():
            return False, "rescale disabled"
        if node_rank not in old_world:
            return False, f"node {node_rank} not in the active world"
        survivors = {
            r: w for r, w in old_world.items() if r != node_rank
        }
        if not survivors:
            return False, "no survivors"
        quorum = env_utils.RESCALE_MIN_QUORUM.get()
        if len(survivors) / len(old_world) < quorum:
            return False, (
                f"{len(survivors)}/{len(old_world)} survivors below "
                f"quorum {quorum:.2f}"
            )
        with self._lock:
            global_batch, micro_batch = self._global_batch, self._micro_batch
            incapable = sorted(set(survivors) - self._capable)
        if global_batch <= 0:
            return False, "no batch config reported"
        if incapable:
            return False, (
                f"survivors {incapable} never advertised a live rescale "
                "engine"
            )
        try:
            derive_accum_schedule(
                global_batch, micro_batch, sum(survivors.values())
            )
        except ValueError as e:
            return False, f"schedule unsatisfiable ({e})"
        return True, ""

    def plan_status(self, plan_id: int) -> Optional[str]:
        """Settlement state of a plan: ``"issued"`` / ``"complete"`` /
        ``"aborted"``, or ``None`` for an unknown id. The remediation
        policy polls this each tick to confirm (or revert) a pending
        quarantine — idempotently, so a failed-over master re-derives
        the same answer from the replayed plan records."""
        with self._lock:
            plan = self._plans.get(int(plan_id))
            return plan.status if plan is not None else None

    def on_node_joined(
        self, node_rank: int, local_world_size: int, rdzv_name: str
    ) -> Optional[m.RescalePlan]:
        """Grow path: a node joined while a frozen world is training.

        The joiner is absorbed into the next round; it boots through the
        normal worker path (it has no live state) and hydrates from the
        shm snapshot, while survivors transition in place.
        """
        if self._replaying or not env_utils.RESCALE.get():
            return None
        if rdzv_name != RendezvousName.TRAINING:
            return None
        mgr = self._rdzv_managers.get(rdzv_name)
        if mgr is None:
            return None
        old_world = mgr.current_world()
        if not old_world or node_rank in old_world:
            return None
        with self._lock:
            if any(
                p.rdzv_name == rdzv_name and p.status == PLAN_ISSUED
                for p in self._plans.values()
            ):
                # One transition at a time; the joiner waits in the
                # rendezvous waiting set until the in-flight plan
                # settles, then triggers again on its next join poll.
                return None
        new_world = dict(old_world)
        new_world[node_rank] = local_world_size
        return self._issue_plan(
            rdzv_name, old_world, new_world, transition="grow"
        )

    def _issue_plan(
        self,
        rdzv_name: str,
        old_world: Dict[int, int],
        new_world: Dict[int, int],
        transition: str,
    ) -> Optional[m.RescalePlan]:
        mgr = self._rdzv_managers.get(rdzv_name)
        if mgr is None:
            return None
        with self._lock:
            global_batch, micro_batch = self._global_batch, self._micro_batch
            snapshot_step = self._last_step
            incapable = sorted(
                set(old_world) & set(new_world) - self._capable
            )
        if global_batch <= 0:
            logger.info(
                "rescale: no batch config reported; falling back to "
                "full restart for the %s", transition,
            )
            return None
        if incapable:
            # Issuing a plan no survivor can apply would hold the fleet
            # for the full apply timeout — training on a stale world —
            # before the inevitable restart. Decline up front instead.
            logger.info(
                "rescale: survivors %s never advertised a live rescale "
                "engine; falling back to full restart for the %s",
                incapable, transition,
            )
            return None
        total_procs = sum(new_world.values())
        try:
            sched = derive_accum_schedule(
                global_batch, micro_batch, total_procs
            )
        except ValueError as e:
            logger.info(
                "rescale: schedule unsatisfiable (%s); falling back to "
                "full restart", e,
            )
            return None
        old_spec, new_spec = self._select_reshape(
            old_world, new_world, global_batch
        )
        new_round = mgr.absorb_world(new_world)
        superseded: List[m.RescalePlan] = []
        with self._lock:
            # A second membership change inside the apply window makes
            # any in-flight plan obsolete: its round is already stale
            # and survivors will pick up the newer plan instead. Abort
            # it WITHOUT invalidating the round — that would fence the
            # new plan's live round and force-restart a healthy world.
            for old in self._plans.values():
                if old.rdzv_name == rdzv_name and old.status == PLAN_ISSUED:
                    old.status = PLAN_ABORTED
                    self._deadlines.pop(old.plan_id, None)
                    superseded.append(old)
            plan = m.RescalePlan(
                plan_id=self._next_plan_id,
                rdzv_name=rdzv_name,
                old_round=new_round - 1,
                new_round=new_round,
                old_world=dict(old_world),
                new_world=dict(new_world),
                global_batch=global_batch,
                micro_batch=sched.micro_batch,
                accum_counts=list(sched.counts),
                snapshot_step=snapshot_step,
                status=PLAN_ISSUED,
                old_spec=old_spec,
                new_spec=new_spec,
            )
            self._next_plan_id += 1
            self._plans[plan.plan_id] = plan
            self._acks[plan.plan_id] = {}
            self._deadlines[plan.plan_id] = (
                time.monotonic() + env_utils.RESCALE_APPLY_TIMEOUT_S.get()  # dtlint: disable=DT011 -- apply deadlines are process-local liveness timers, deliberately re-armed from the live clock and knob on every run
            )
        for old in superseded:
            self._journal({
                "rec": "abort", "plan_id": old.plan_id,
                "reason": "superseded",
            })
            logger.info(
                "rescale plan %s superseded by plan %s before settling",
                old.plan_id, plan.plan_id,
            )
            emit(  # dtlint: disable=DT012 -- replay-guarded at the sink: JobMaster._event_sink drops emits while store.replaying
                EventKind.RESCALE_ABORT, _role="master",
                plan_id=old.plan_id, reason="superseded",
            )
        self._journal({"rec": "plan", "plan": asdict(plan)})
        diff = ""
        if plan.reshapes:
            from dlrover_tpu.accel.search import spec_diff

            diff = spec_diff(plan.old_spec, plan.new_spec)
            select = {
                "rec": "select", "plan_id": plan.plan_id,
                "old_spec": dict(plan.old_spec),
                "new_spec": dict(plan.new_spec), "diff": diff,
            }
            with self._lock:
                self._last_select = select
            self._journal_reshape(select)
        logger.info(
            "rescale plan %s: %s %s -> %s (round %s -> %s, accum %s, "
            "snapshot_step %s%s)", plan.plan_id, transition,
            sorted(old_world), sorted(new_world), plan.old_round,
            plan.new_round, plan.accum_counts, plan.snapshot_step,
            f", reshape {diff}" if diff else "",
        )
        emit(  # dtlint: disable=DT012 -- replay-guarded at the sink: JobMaster._event_sink drops emits while store.replaying
            EventKind.RESCALE_PLAN, _role="master",
            plan_id=plan.plan_id, transition=transition,
            old_world=sorted(old_world), new_world=sorted(new_world),
            old_round=plan.old_round, new_round=plan.new_round,
            **({"spec_diff": diff} if diff else {}),
        )
        return plan

    def _select_reshape(
        self,
        old_world: Dict[int, int],
        new_world: Dict[int, int],
        global_batch: int,
    ) -> tuple:
        """Pick the surviving world's ParallelSpec via the constrained
        search (``accel/search.py``). Returns ``(old_spec, new_spec)``
        as asdict dicts, or ``({}, {})`` to keep the plan DP-only —
        which is correct whenever the fleet never reported its mesh
        (``set_parallel_config``), runs a trivial 1-device spec, or the
        member→device mapping is not integral. Search failures degrade
        to DP-only, never to a lost plan."""
        with self._lock:
            spec_d = dict(self._spec)
            profile_d = dict(self._profile)
            hbm = self._hbm
        if not env_utils.RESCALE_RESHAPE.get() or not spec_d:  # dtlint: disable=DT011 -- never reached on replay: _issue_plan is guarded by _replaying in both triggers; plans replay via their journaled record
            return {}, {}
        try:
            import dataclasses as _dc

            from dlrover_tpu.accel.search import (
                ModelProfile,
                search_reshape_spec,
                spec_from_dict,
            )

            cur = spec_from_dict(spec_d)
            old_procs = sum(old_world.values())
            new_procs = sum(new_world.values())
            if cur.total <= 1 or old_procs <= 0:
                return {}, {}
            if cur.total % old_procs:
                # No integral member→device mapping: the mesh does not
                # shrink/grow proportionally with membership, so there
                # is nothing principled to search against.
                return {}, {}
            n_devices = (cur.total // old_procs) * new_procs
            fields = {f.name for f in _dc.fields(ModelProfile)}
            profile = ModelProfile(**{
                k: v for k, v in profile_d.items() if k in fields
            })
            # Measured link profile (when the aggregator has one): the
            # search prices candidates at live per-axis bandwidth and
            # the collective-strategy dimension opens up.
            link_profile = None
            if self._link_profile_fn is not None:
                try:
                    link_profile = self._link_profile_fn()
                except Exception:
                    logger.debug(
                        "link profile fetch failed", exc_info=True
                    )
            hosts = len(new_world)
            dph = (
                n_devices // hosts
                if hosts > 1 and n_devices % hosts == 0 else 0
            )
            found = search_reshape_spec(
                profile, n_devices, global_batch,
                hbm or 16e9, current_spec=cur,
                stickiness=env_utils.RESCALE_RESHAPE_STICKINESS.get(),  # dtlint: disable=DT011 -- same guard: spec selection only runs live; the chosen spec is journaled in the plan record
                devices_per_host=dph, link_profile=link_profile,
            )
            if found is None:
                return {}, {}
            return spec_d, _dc.asdict(found[0])
        except Exception as e:
            logger.warning(
                "reshape spec search failed (%s); issuing a DP-only "
                "plan", e,
            )
            return {}, {}

    # ---------------- delivery / acks ----------------
    def get_plan(
        self, rdzv_name: str, node_rank: int, round_: int
    ) -> m.RescalePlan:
        """Answer a survivor's poll: the newest issued plan that covers
        it and supersedes the round it is running. A node that missed an
        intermediate plan correctly applies only the newest one — the
        transition engine re-shards from its *current* state, not from
        ``plan.old_world``."""
        best = m.RescalePlan()
        with self._lock:
            for plan in self._plans.values():
                if (
                    plan.rdzv_name == rdzv_name
                    and plan.status == PLAN_ISSUED
                    and node_rank in plan.new_world
                    and plan.new_round > round_
                    and plan.new_round > best.new_round
                ):
                    best = plan
        if best.exists:
            ev = fault_hit(
                ChaosSite.RESCALE_PLAN_DELIVER,
                detail=f"plan{best.plan_id}:rank{node_rank}",
            )
            if ev is not None:
                if ev.kind == "delay":
                    time.sleep(ev.delay_s)
                elif ev.kind == "drop":
                    return m.RescalePlan()
        return best

    def apply_ack(
        self, plan_id: int, node_rank: int, ok: bool, error: str = ""
    ) -> bool:
        """Record one survivor's ack (reached via the journaled
        ``RescaleAck`` RPC, so replay re-derives plan outcomes). All
        survivors ok → complete; any failure → abort + invalidate the
        round so survivors fall back to a full restart."""
        aborted = completed = False
        with self._lock:
            plan = self._plans.get(plan_id)
            if plan is None:
                return False
            if plan.status != PLAN_ISSUED:
                # Late ack for a settled plan: acknowledged, no effect.
                return True
            self._acks[plan_id][node_rank] = ok
            if not ok:
                plan.status = PLAN_ABORTED
                aborted = True
            else:
                acks = self._acks[plan_id]
                if all(acks.get(r) for r in plan_survivors(plan)):
                    plan.status = PLAN_COMPLETE
                    completed = True
            rdzv_name = plan.rdzv_name
            new_round = plan.new_round
            reshape_diff = ""
            if plan.reshapes:
                from dlrover_tpu.accel.search import spec_diff

                reshape_diff = spec_diff(plan.old_spec, plan.new_spec)
        if self._replaying:
            return True
        if aborted:
            logger.error(
                "rescale plan %s (round %s%s) aborted by node %s: %s; "
                "invalidating round %s for full restart", plan_id,
                new_round,
                f", reshape {reshape_diff}" if reshape_diff else "",
                node_rank, error, new_round,
            )
            emit(  # dtlint: disable=DT012 -- replay-guarded at the sink: JobMaster._event_sink drops emits while store.replaying
                EventKind.RESCALE_ABORT, _node_id=node_rank,
                _role="master", plan_id=plan_id, reason=error or "nack",
                round=new_round,
                **({"spec_diff": reshape_diff} if reshape_diff else {}),
            )
            self._invalidate_if_current(rdzv_name, new_round)
        elif completed:
            logger.info("rescale plan %s complete: every survivor "
                        "transitioned in place", plan_id)
            emit(  # dtlint: disable=DT012 -- replay-guarded at the sink: JobMaster._event_sink drops emits while store.replaying
                EventKind.RESCALE_COMPLETE, _role="master",
                plan_id=plan_id, new_round=new_round,
            )
        return True

    def supersede_plan(self, plan_id: int, reason: str) -> bool:
        """Abort an in-flight plan WITHOUT invalidating its round.

        The preemption plane's false-alarm cancel: the shrink plan it
        issued proactively is obsolete because the victim stays, and
        fencing the live round would force-restart a healthy world.
        Survivors that already applied keep training; a settled plan
        (complete or already aborted) is left untouched.
        """
        with self._lock:
            plan = self._plans.get(plan_id)
            if plan is None or plan.status != PLAN_ISSUED:
                return False
            plan.status = PLAN_ABORTED
            self._deadlines.pop(plan_id, None)
        self._journal({
            "rec": "abort", "plan_id": plan_id, "reason": reason,
        })
        logger.info(
            "rescale plan %s superseded (%s); round left valid",
            plan_id, reason,
        )
        emit(
            EventKind.RESCALE_ABORT, _role="master",
            plan_id=plan_id, reason=reason,
        )
        return True

    def tick(self):
        """Periodic driver (master monitor loop): abort plans whose
        survivors did not all ack within the apply timeout."""
        if self._replaying:
            return
        now = time.monotonic()
        expired: List[m.RescalePlan] = []
        with self._lock:
            for plan_id, deadline in list(self._deadlines.items()):
                plan = self._plans.get(plan_id)
                if plan is None or plan.status != PLAN_ISSUED:
                    self._deadlines.pop(plan_id, None)
                    continue
                if now >= deadline:
                    plan.status = PLAN_ABORTED
                    self._deadlines.pop(plan_id, None)
                    expired.append(plan)
        for plan in expired:
            self._journal({
                "rec": "abort", "plan_id": plan.plan_id,
                "reason": "apply-timeout",
            })
            logger.error(
                "rescale plan %s timed out waiting for survivor acks; "
                "invalidating round %s for full restart",
                plan.plan_id, plan.new_round,
            )
            emit(
                EventKind.RESCALE_ABORT, _role="master",
                plan_id=plan.plan_id, reason="apply-timeout",
            )
            self._invalidate_if_current(plan.rdzv_name, plan.new_round)

    def _invalidate_if_current(self, rdzv_name: str, new_round: int):
        """Fence ``new_round`` for the full-restart fallback — but only
        while it is still the rendezvous manager's newest round. A plan
        that aborts after a newer plan already moved the world on must
        not force-restart that healthy, already-transitioned round."""
        mgr = self._rdzv_managers.get(rdzv_name)
        if mgr is None:
            return
        current = getattr(mgr, "current_round", lambda: new_round)()
        if current == new_round:
            mgr.invalidate_round()
        else:
            logger.info(
                "rescale: round %s already superseded by round %s; "
                "skipping invalidation", new_round, current,
            )

    # ---------------- durability ----------------
    def checkpoint(self) -> dict:
        with self._lock:
            return {
                "plans": [asdict(p) for p in self._plans.values()],
                "acks": {k: dict(v) for k, v in self._acks.items()},
                "next_plan_id": self._next_plan_id,
                "global_batch": self._global_batch,
                "micro_batch": self._micro_batch,
                "last_step": self._last_step,
                "capable": sorted(self._capable),
                "spec": dict(self._spec),
                "profile": dict(self._profile),
                "hbm": self._hbm,
                "last_select": dict(self._last_select),
            }

    def restore(self, state: dict):
        if not state:
            return
        with self._lock:
            for d in state.get("plans", []):
                plan = m.RescalePlan(**d)
                self._plans[plan.plan_id] = plan
                # A plan in flight across a master relaunch gets a fresh
                # apply window rather than an instant timeout-abort.
                if plan.status == PLAN_ISSUED:
                    self._deadlines[plan.plan_id] = (
                        time.monotonic()
                        + env_utils.RESCALE_APPLY_TIMEOUT_S.get()
                    )
            for pid, acks in state.get("acks", {}).items():
                self._acks[int(pid)] = {
                    int(r): bool(ok) for r, ok in acks.items()
                }
            self._next_plan_id = max(
                self._next_plan_id, int(state.get("next_plan_id", 1))
            )
            self._global_batch = int(
                state.get("global_batch", self._global_batch)
            )
            self._micro_batch = int(
                state.get("micro_batch", self._micro_batch)
            )
            self._last_step = max(
                self._last_step, int(state.get("last_step", -1))
            )
            self._capable.update(
                int(r) for r in state.get("capable", [])
            )
            if state.get("spec"):
                self._spec = dict(state["spec"])
            if state.get("profile"):
                self._profile = dict(state["profile"])
            self._hbm = float(state.get("hbm", self._hbm))
            if state.get("last_select"):
                self._last_select = dict(state["last_select"])

    def replay(self, payload: Dict[str, Any]):
        """Re-apply one journaled ``("rescale", payload, ts)`` record.

        Pure bookkeeping — no emits, no rendezvous side effects: the
        rendezvous round counters replay through their own ``rdzv``
        records and events through ``event`` records.
        """
        rec = payload.get("rec")
        if rec == "config":
            with self._lock:
                self._global_batch = int(payload.get("global_batch", 0))
                self._micro_batch = int(payload.get("micro_batch", 0))
        elif rec == "plan":
            with self._lock:
                plan = m.RescalePlan(**payload["plan"])
                self._plans[plan.plan_id] = plan
                self._acks.setdefault(plan.plan_id, {})
                self._next_plan_id = max(
                    self._next_plan_id, plan.plan_id + 1
                )
                if plan.status == PLAN_ISSUED:
                    self._deadlines[plan.plan_id] = (
                        time.monotonic()  # dtlint: disable=DT011 -- a replayed in-flight plan intentionally gets a fresh apply window; the deadline is a process-local timer, not journaled state
                        + env_utils.RESCALE_APPLY_TIMEOUT_S.get()  # dtlint: disable=DT011 -- same fresh apply window: the knob is a liveness timer input, not journaled state
                    )
        elif rec == "capable":
            with self._lock:
                self._capable.add(int(payload.get("node", -1)))
        elif rec == "abort":
            with self._lock:
                plan = self._plans.get(int(payload.get("plan_id", -1)))
                if plan is not None:
                    plan.status = PLAN_ABORTED
        else:
            logger.warning("skipping unknown rescale record %r", rec)

    def replay_reshape(self, payload: Dict[str, Any]):
        """Re-apply one journaled ``("reshape", payload, ts)`` record.

        Pure overwrite bookkeeping: ``config`` restores the spec-search
        inputs (``set_parallel_config``'s snapshot), ``select`` restores
        the last searched transition. The chosen spec itself rides in
        the plan's own ``("rescale", ...)`` record — the search NEVER
        re-runs on replay."""
        rec = payload.get("rec")
        if rec == "config":
            with self._lock:
                self._spec = dict(payload.get("spec", {}))
                self._profile = dict(payload.get("profile", {}))
                hbm = float(payload.get("hbm", 0.0))
                if hbm > 0:
                    self._hbm = hbm
        elif rec == "select":
            with self._lock:
                self._last_select = dict(payload)
        else:
            logger.warning("skipping unknown reshape record %r", rec)
