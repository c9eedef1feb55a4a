"""The per-host elastic agent.

Capability parity with the reference's ``elastic_agent/torch/training.py``:

- ``ElasticLaunchConfig`` — launch knobs (min/max nodes, procs per node,
  device check, restarts, straggler policy).
- ``MasterRendezvousHandler`` — rendezvous *through the master* (join RPC +
  comm-world polling), not through a c10d store.
- ``ElasticTrainingAgent`` — spawns one training process per local worker,
  assigns global ranks from the frozen world, monitors processes, reports
  failures, flushes the shm flash-checkpoint on death, and restarts workers
  on failure or membership change.

TPU specifics: workers are JAX processes; the agent hands each one
``DLROVER_TPU_COORDINATOR_ADDR`` / ``PROCESS_ID`` / ``NUM_PROCESSES`` so the
trainer's :func:`dlrover_tpu.train.init_training` can call
``jax.distributed.initialize``. The JAX runtime cannot change world size
in-process, so every recovery is a worker restart + flash-checkpoint
restore — the same model the reference uses for NCCL.
"""

import os
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.agent import tpu_chips
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.chaos.sites import ChaosSite
from dlrover_tpu.common import env_utils
from dlrover_tpu.common.backoff import ExponentialBackoff
from dlrover_tpu.common.constants import (
    NodeEnv,
    NodeStatus,
    RendezvousName,
    TrainingExceptionLevel,
)
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.rpc import find_free_port
from dlrover_tpu.observability.events import (
    EventKind,
    emit,
    flush_events,
    set_identity,
)


@dataclass
class ElasticLaunchConfig:
    min_nodes: int = 1
    max_nodes: int = 1
    nproc_per_node: int = 1
    node_rank: int = 0
    job_name: str = "local-job"
    rdzv_timeout: float = 600.0
    waiting_timeout: float = 30.0
    monitor_interval: float = 1.0
    max_restarts: int = 3
    network_check: bool = False
    exclude_straggler: bool = False
    node_unit: int = 1
    log_dir: str = ""
    # Extra env vars for every worker.
    worker_env: Dict[str, str] = field(default_factory=dict)


class RendezvousOutcome:
    def __init__(self, round_: int, world: Dict[int, int], node_rank: int,
                 coordinator_addr: str):
        self.round = round_
        self.world = world  # node_rank -> local_world_size
        self.node_rank = node_rank
        self.coordinator_addr = coordinator_addr
        ranks = sorted(world)
        self.node_index = ranks.index(node_rank)
        self.num_nodes = len(ranks)
        self.world_size = sum(world.values())
        self.rank_offset = sum(world[r] for r in ranks[: self.node_index])

    def adopt(self, round_: int, world: Dict[int, int]):
        """Re-derive this outcome for a new round/world without a
        rendezvous (an in-place rescale transition)."""
        self.round = round_
        self.world = dict(world)
        ranks = sorted(self.world)
        self.node_index = ranks.index(self.node_rank)
        self.num_nodes = len(ranks)
        self.world_size = sum(self.world.values())
        self.rank_offset = sum(
            self.world[r] for r in ranks[: self.node_index]
        )


class MasterRendezvousHandler:
    """Rendezvous via master RPCs (parity: training.py:137)."""

    def __init__(self, client: MasterClient, rdzv_name: str, node_rank: int,
                 local_world_size: int, timeout: float = 600.0):
        self._client = client
        self._name = rdzv_name
        self._node_rank = node_rank
        self._local_world_size = local_world_size
        self._timeout = timeout

    def next_rendezvous(self) -> RendezvousOutcome:
        self._client.join_rendezvous(
            self._name, self._node_rank, self._local_world_size
        )
        deadline = time.monotonic() + self._timeout
        backoff = ExponentialBackoff(initial=0.1, max_delay=1.0)
        while time.monotonic() < deadline:
            round_, _, world = self._client.get_comm_world(
                self._name, self._node_rank
            )
            if world and self._node_rank in world:
                coordinator = self._setup_coordinator(round_, world)
                return RendezvousOutcome(
                    round_, world, self._node_rank, coordinator
                )
            if world and self._node_rank not in world:
                # Frozen without us (node_unit clipping): rejoin next round.
                self._client.join_rendezvous(
                    self._name, self._node_rank, self._local_world_size
                )
            backoff.sleep(deadline - time.monotonic())
        raise TimeoutError(
            f"rendezvous {self._name} did not complete within {self._timeout}s"
        )

    def _setup_coordinator(self, round_: int, world: Dict[int, int]) -> str:
        """The lowest node rank hosts the JAX coordinator; its address is
        published through the master kv-store, keyed by round."""
        key = f"coordinator/{self._name}/{round_}"
        first = sorted(world)[0]
        if self._node_rank == first:
            host = env_utils.HOST_IP.get()
            addr = f"{host}:{find_free_port()}"
            self._client.kv_store_set(key, addr.encode())
            return addr
        return self._client.kv_store_wait([key], timeout=60.0)[key].decode()


class WorkerSpec:
    def __init__(self, entrypoint: str, args: List[str]):
        self.entrypoint = entrypoint
        self.args = args


class ElasticTrainingAgent:
    """Spawn/supervise local training processes (parity: training.py:318)."""

    def __init__(self, config: ElasticLaunchConfig, spec: WorkerSpec,
                 client: Optional[MasterClient] = None):
        self._config = config
        self._spec = spec
        self._client = client or MasterClient.singleton_instance()
        self._workers: List[subprocess.Popen] = []
        self._restart_count = 0
        self._ckpt_saver = None  # wired by start_saver()
        self._stopped = threading.Event()
        # Heartbeat coalescing (DLROVER_TPU_AGENT_BEAT): monitors deposit
        # their newest observations here and the periodic beat folds them
        # into ONE AgentBeat RPC — at 10k agents the master sees one
        # request per agent per interval instead of three.
        self._beat_mode = env_utils.AGENT_BEAT.get()
        self._beat_lock = threading.Lock()
        self._beat_step: Tuple[int, float] = (-1, 0.0)
        self._beat_probe: Optional[Dict] = None

    # ---------------- checkpoint saver hook ----------------
    def start_saver(self):
        """Start the async flash-checkpoint saver thread in this process."""
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

        AsyncCheckpointSaver.start_async_saving_ckpt(self._config.node_rank)

    def _save_shm_to_storage(self):
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
        from dlrover_tpu.utils.tracing import get_tracer

        saver = AsyncCheckpointSaver.get_ckpt_saver()
        if saver is not None:
            try:
                with get_tracer().span("ckpt-crash-flush"):
                    saver.save_shm_to_storage()
            except Exception:
                logger.exception("flash-checkpoint crash flush failed")

    # ---------------- run loop ----------------
    def _note_step(self, step: int, ts: float):
        """TrainingMonitor sink: keep the newest observation for the
        next beat. Monotonic max — a restarted worker replaying earlier
        steps still refreshes the timestamp (liveness first)."""
        with self._beat_lock:
            self._beat_step = (max(step, self._beat_step[0]), ts)

    def _note_probe(self, sample: Dict):
        """LinkProbe sink: latest-wins — the straggler profile wants the
        current link state, not a backlog of stale samples."""
        with self._beat_lock:
            self._beat_probe = sample

    def _send_beat(self):
        with self._beat_lock:
            step, step_ts = self._beat_step
            probe = self._beat_probe
            # Clear after snapshot: a beat only carries step progress the
            # monitors observed since the last one, so the master's hang
            # detection still sees silence when workers stop writing
            # metrics (a sticky step would mask the hang forever).
            self._beat_step = (-1, 0.0)
            self._beat_probe = None
        self._client.report_beat(
            step=step, step_ts=step_ts, probe=probe or {}
        )

    def _start_heartbeats(self):
        """Agent-level liveness, independent of worker state: covers the
        stop-workers/re-rendezvous gaps so the master's heartbeat monitor
        never mistakes a restarting agent for a dead one."""
        from dlrover_tpu.common.periodic import PeriodicTask

        self._heartbeat_task = PeriodicTask(
            self._send_beat if self._beat_mode
            else self._client.report_heartbeat,
            self._config.monitor_interval,
            "agent-heartbeat",
        )
        self._heartbeat_task.start()

    def _start_monitors(self):
        from dlrover_tpu.agent.monitor import ResourceMonitor, TrainingMonitor
        from dlrover_tpu.common.constants import ConfigPath
        from dlrover_tpu.common.global_context import get_context

        interval = get_context().reporting_interval
        self._resource_monitor = ResourceMonitor(
            self._client, interval=interval
        )
        self._resource_monitor.start()
        # Workers drop per-step metrics here (train.report_training_metrics)
        # and the monitor forwards them — a job-unique default so stock
        # deployments get the liveness channel without any configuration.
        self._metrics_path = os.getenv(ConfigPath.ENV_RUNTIME_METRICS) or (
            os.path.join(
                ConfigPath.ROOT,
                f"runtime_metrics_{self._config.job_name}"
                f"_n{self._config.node_rank}.jsonl",
            )
        )
        self._training_monitor = TrainingMonitor(
            self._metrics_path, self._client,
            step_sink=self._note_step if self._beat_mode else None,
        )
        self._training_monitor.start()
        # The tuner loop only runs when auto-tuning is enabled (same gate
        # as the master's strategy generator): with it off, polling every
        # few seconds and pointing workers at a never-written file would
        # be pure overhead.
        self._config_tuner = None
        if get_context().auto_paral_tuning:
            from dlrover_tpu.agent.config_tuner import ParalConfigTuner

            self._config_tuner = ParalConfigTuner(self._client)
            self._config_tuner.start()
        # Continuous link telemetry (probe.link events feeding the
        # master's straggler detector); DLROVER_TPU_PROBE_INTERVAL=0
        # leaves it off.
        from dlrover_tpu.agent.device_check import LinkProbe

        self._link_probe = LinkProbe(
            self._client,
            sink=self._note_probe if self._beat_mode else None,
        )
        self._link_probe.start()
        # Shard-lease broker (DLROVER_TPU_SHARD_LEASE_PLANE): sub-leases
        # bulk shard grants to this node's workers over shm, so the
        # steady-state data path makes zero per-worker master RPCs.
        self._shard_broker = None
        plane_cfg = env_utils.SHARD_LEASE_PLANE.get()
        if plane_cfg:
            from dlrover_tpu.agent.shard_broker import ShardLeaseBroker

            # "auto" = a per-node name; anything else is used verbatim
            # (shared-host test jobs must not collide on the segment).
            plane_name = (
                f"shard_plane_{self._config.job_name}"
                f"_n{self._config.node_rank}"
                if plane_cfg == "auto" else plane_cfg
            )
            self._shard_broker = ShardLeaseBroker(self._client, plane_name)
            self._shard_broker.start()
        # Preemption watcher: notice sources -> journaled report + grace
        # flush, so the master can shrink in place before the kill.
        from dlrover_tpu.agent.preempt import PreemptionWatcher

        self._preempt_watcher = PreemptionWatcher(
            client=self._client,
            node_rank=self._config.node_rank,
            flush_fn=self._save_shm_to_storage,
            kill_fn=self._kill_all_workers,
        )
        self._preempt_watcher.start()

    def run(self) -> int:
        self._start_heartbeats()
        self._start_monitors()
        self._client.report_rdzv_params(
            self._config.min_nodes,
            self._config.max_nodes,
            self._config.waiting_timeout,
            self._config.node_unit,
        )
        if self._config.network_check:
            from dlrover_tpu.agent.device_check import run_device_check

            ok = run_device_check(self._config, self._client)
            if not ok:
                logger.error("device check flagged this node as faulty")
                self._client.report_node_status(
                    NodeStatus.FAILED, "hardware-error"
                )
                return 1
        self.start_saver()
        while self._restart_count <= self._config.max_restarts:
            outcome = self._rendezvous()
            self._start_workers(outcome)
            result = self._monitor_workers(outcome)
            self._stop_workers()
            if result == "succeeded":
                self._client.report_node_status(NodeStatus.SUCCEEDED)
                return 0
            if result == "failed":
                self._restart_count += 1
                logger.info(
                    "workers failed; restart %s/%s",
                    self._restart_count, self._config.max_restarts,
                )
            elif result == "membership_changed":
                logger.info("membership changed; re-forming rendezvous")
            elif result == "stopped":
                return 1
            from dlrover_tpu.utils.tracing import get_tracer

            get_tracer().instant(
                f"workers-{result}", restart=self._restart_count
            )
            get_tracer().export()  # no-op unless DLROVER_TPU_TRACE_FILE
            # Reaching here means the loop restarts the workers (failure
            # or membership change).
            emit(
                EventKind.WORKER_RESTART, reason=result,
                restart=self._restart_count,
            )
        self._client.report_node_status(NodeStatus.FAILED, "fatal-error")
        return 1

    def _rendezvous(self) -> RendezvousOutcome:
        from dlrover_tpu.utils.tracing import get_tracer

        handler = MasterRendezvousHandler(
            self._client,
            RendezvousName.TRAINING,
            self._config.node_rank,
            self._config.nproc_per_node,
            self._config.rdzv_timeout,
        )
        with get_tracer().span(
            "rendezvous", node_rank=self._config.node_rank,
            restart=self._restart_count,
        ):
            outcome = handler.next_rendezvous()
        logger.info(
            "rendezvous round %s: %s nodes, world size %s, coordinator %s",
            outcome.round, outcome.num_nodes, outcome.world_size,
            outcome.coordinator_addr,
        )
        return outcome

    def _worker_env(self, outcome: RendezvousOutcome, local_rank: int) -> Dict:
        from dlrover_tpu.common.constants import ConfigPath

        env = dict(os.environ)
        env.update(self._config.worker_env)
        if getattr(self, "_config_tuner", None) is not None:
            # Workers hot-reload the tuned parallel config from this file
            # (ElasticDataLoader.load_config).
            env[ConfigPath.ENV_PARAL_CONFIG] = self._config_tuner.path
        if getattr(self, "_metrics_path", ""):
            env[ConfigPath.ENV_RUNTIME_METRICS] = self._metrics_path
        if getattr(self, "_shard_broker", None) is not None:
            # Workers' ShardingClients attach to this node's sub-lease
            # plane instead of fetching shards over RPC.
            env[env_utils.SHARD_LEASE_PLANE.name] = (
                self._shard_broker.plane_name
            )
        env.update(
            {
                NodeEnv.JOB_NAME: self._config.job_name,
                NodeEnv.MASTER_ADDR: self._client.master_addr,
                NodeEnv.NODE_ID: str(self._config.node_rank),
                NodeEnv.NODE_RANK: str(self._config.node_rank),
                NodeEnv.NODE_NUM: str(outcome.num_nodes),
                NodeEnv.COORDINATOR_ADDR: outcome.coordinator_addr,
                NodeEnv.PROCESS_ID: str(outcome.rank_offset + local_rank),
                NodeEnv.NUM_PROCESSES: str(outcome.world_size),
                NodeEnv.LOCAL_RANK: str(local_rank),
                NodeEnv.LOCAL_WORLD_SIZE: str(self._config.nproc_per_node),
                NodeEnv.RESTART_COUNT: str(self._restart_count),
                # Restart-latency attribution: workers measure their
                # spawn->entry phase against this stamp.
                env_utils.SPAWN_TS.name: repr(time.time()),
            }
        )
        return env

    def _start_workers(self, outcome: RendezvousOutcome):
        from dlrover_tpu.agent.forkserver import ForkServer

        self._workers = []
        use_forkserver = ForkServer.enabled()
        if use_forkserver:
            # The template imports jax with the AGENT's env; per-worker
            # overrides of import-sensitive vars would silently not
            # apply in a forked child — fall back to real spawns.
            sensitive = {
                k: v for k, v in self._config.worker_env.items()
                if k.startswith(("JAX_", "XLA_"))
            }
            if any(os.environ.get(k) != v for k, v in sensitive.items()):
                logger.warning(
                    "worker_env overrides import-sensitive vars %s; "
                    "disabling the fork server for this job",
                    sorted(sensitive),
                )
                use_forkserver = False
        if use_forkserver:
            if getattr(self, "_forkserver", None) is None:
                self._forkserver = ForkServer()
            try:
                # First start pays the preload (~2 s); every restart
                # after that forks in milliseconds — the spawn_s lever
                # of the restart-latency breakdown.
                self._forkserver.start()
            except Exception as e:
                logger.warning(
                    "fork server unavailable (%s); falling back to "
                    "subprocess spawn", e,
                )
                use_forkserver = False
        # Several workers on a TPU host: one chip each (tpu_chips.py).
        # The libtpu mesh ports are drawn once so every worker gets the
        # same address list.
        chip_ports: List[int] = []
        nproc = self._config.nproc_per_node
        if nproc > 1 and tpu_chips.count_tpu_chips():
            while len(chip_ports) < nproc:
                port = find_free_port()
                if port not in chip_ports:
                    chip_ports.append(port)
        for local_rank in range(nproc):
            env = self._worker_env(outcome, local_rank)
            if chip_ports:
                env.update(tpu_chips.worker_chip_env(local_rank, chip_ports))
            log_path = ""
            if self._config.log_dir:
                os.makedirs(self._config.log_dir, exist_ok=True)
                rank = outcome.rank_offset + local_rank
                log_path = os.path.join(
                    self._config.log_dir, f"rank{rank}.log"
                )
            if use_forkserver:
                proc = self._forkserver.spawn(
                    self._spec.entrypoint, self._spec.args, env,
                    log_path=log_path,
                )
            else:
                cmd = [
                    sys.executable, self._spec.entrypoint,
                    *self._spec.args,
                ]
                stdout = stderr = None
                if log_path:
                    stdout = open(log_path, "ab")
                    stderr = subprocess.STDOUT
                proc = subprocess.Popen(
                    cmd, env=env, stdout=stdout, stderr=stderr,
                    start_new_session=True,
                )
            self._workers.append(proc)
        self._client.report_node_status(NodeStatus.RUNNING)
        logger.info("started %s worker processes%s", len(self._workers),
                    " (fork server)" if use_forkserver else "")

    def _chaos_hit_workers(self):
        """Scripted worker kill/hang (chaos drills).

        Fires from the monitor loop so the resulting failure travels the
        REAL detection path: a killed worker is seen as a nonzero exit by
        the next poll; a hung (SIGSTOPped) one stops heartbeating and is
        flagged by the master's hang detection."""
        from dlrover_tpu.chaos.injector import fault_hit

        event = fault_hit(ChaosSite.AGENT_MONITOR)
        if event is None:
            return
        local_rank = int(event.args.get("rank", 0))
        if local_rank >= len(self._workers):
            return
        proc = self._workers[local_rank]
        if proc.poll() is not None:
            return
        try:
            pgid = os.getpgid(proc.pid)
        except ProcessLookupError:
            return
        if event.kind == "kill":
            logger.warning(
                "CHAOS: SIGKILL worker local_rank=%s pid=%s",
                local_rank, proc.pid,
            )
            os.killpg(pgid, signal.SIGKILL)
        elif event.kind == "hang":
            logger.warning(
                "CHAOS: SIGSTOP worker local_rank=%s pid=%s",
                local_rank, proc.pid,
            )
            os.killpg(pgid, signal.SIGSTOP)
            resume_after = float(event.args.get("resume_after_s", 0))
            if resume_after > 0:
                def _resume():
                    try:
                        os.killpg(pgid, signal.SIGCONT)
                    except (ProcessLookupError, PermissionError):
                        pass

                threading.Timer(resume_after, _resume).start()

    def _kill_all_workers(self):
        """Node-level kill, as the platform delivers it (chaos preempt
        drills): every live worker group gets SIGKILL at once."""
        logger.warning("CHAOS: preemption kill of all local workers")
        for proc in self._workers:
            if proc.poll() is not None:
                continue
            try:
                os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass

    def _monitor_workers(self, outcome: RendezvousOutcome) -> str:
        while not self._stopped.is_set():
            # Interruptible: stop() wakes the monitor immediately
            # instead of leaving it asleep for a full poll interval.
            if self._stopped.wait(self._config.monitor_interval):
                break
            self._chaos_hit_workers()
            codes = [p.poll() for p in self._workers]
            if any(c is not None and c != 0 for c in codes):
                failed = [
                    (i, c) for i, c in enumerate(codes) if c not in (None, 0)
                ]
                logger.error("worker processes failed: %s", failed)
                # An exit inside an active preemption window is the
                # announced kill, not a crash — the ledger/timeline
                # book it under preempt:handled instead.
                watcher = getattr(self, "_preempt_watcher", None)
                cause = (
                    "preempt" if watcher is not None and watcher.active
                    else "crash"
                )
                emit(
                    EventKind.WORKER_FAIL, codes=failed,
                    restart=self._restart_count, cause=cause,
                )
                self._client.report_failure(
                    f"worker exit codes {failed}",
                    level=TrainingExceptionLevel.PROCESS_ERROR,
                    restart_count=self._restart_count,
                )
                self._save_shm_to_storage()
                return "failed"
            if all(c == 0 for c in codes):
                return "succeeded"
            try:
                waiting = self._client.num_nodes_waiting(RendezvousName.TRAINING)
                stale = self._client.world_stale(
                    RendezvousName.TRAINING, outcome.round
                )
            except Exception as e:
                logger.warning("master unreachable from monitor loop: %s", e)
                continue
            if stale:
                if self._try_rescale_in_place(outcome):
                    continue
                # No in-place plan (rescale off, quorum lost, plan
                # aborted...): flush the shm checkpoint and re-form
                # without the dead member.
                logger.info(
                    "round %s invalidated by a member death; re-forming",
                    outcome.round,
                )
                self._save_shm_to_storage()
                return "membership_changed"
            if waiting > 0:
                # A joiner is normally absorbed by a grow plan (which
                # also stales our round); persistent waiters mean the
                # coordinator declined — full restart.
                if self._try_rescale_in_place(outcome):
                    continue
                self._save_shm_to_storage()
                return "membership_changed"
        return "stopped"

    def _try_rescale_in_place(self, outcome: RendezvousOutcome) -> bool:
        """Stale round: wait for a rescale plan covering this node and
        for it to settle. The workers apply the plan themselves (their
        trainers poll the same RPC and re-shard live state); the agent
        only keeps them alive and adopts the new round. Returns True
        when the transition completed and monitoring should continue."""
        if not env_utils.RESCALE.get():
            return False
        interval = max(0.05, env_utils.RESCALE_POLL_INTERVAL_S.get())
        deadline = (
            time.monotonic() + env_utils.RESCALE_APPLY_TIMEOUT_S.get()
        )
        # Short grace for the plan to appear: the coordinator issues it
        # in the same call that staled the round, so "no plan" after a
        # few polls means it declined (full-restart fallback).
        grace = time.monotonic() + max(3.0, 5 * interval)
        plan = None
        while not self._stopped.is_set() and time.monotonic() < deadline:
            try:
                found = self._client.get_rescale_plan(
                    RendezvousName.TRAINING, self._config.node_rank,
                    outcome.round,
                )
            except Exception as e:
                logger.warning("rescale plan poll failed: %s", e)
                return False
            if found.exists:
                plan = found
                break
            if time.monotonic() >= grace:
                return False
            self._stopped.wait(interval)
        if plan is None:
            return False
        logger.info(
            "rescale plan %s covers this node: world %s -> %s (round "
            "%s -> %s); holding workers for in-place transition",
            plan.plan_id, sorted(plan.old_world), sorted(plan.new_world),
            plan.old_round, plan.new_round,
        )
        from dlrover_tpu.agent.device_check import LinkProbe

        # The workers' d2d resharding transfers run inside this settle
        # window; bracket it so concurrent link-probe samples carry the
        # transfer flag (the master's link aggregator keeps them out of
        # its saturation baseline — transition traffic is not link
        # degradation).
        with LinkProbe.transfer_window():
            return self._settle_rescale_plan(outcome, plan, deadline, interval)

    def _settle_rescale_plan(self, outcome, plan, deadline, interval) -> bool:
        while not self._stopped.is_set() and time.monotonic() < deadline:
            if any(
                p.poll() not in (None, 0) for p in self._workers
            ):
                # A worker died mid-transition; let the failure path
                # handle it on the next monitor pass.
                return False
            try:
                aborted = self._client.world_stale(
                    RendezvousName.TRAINING, plan.new_round
                )
                still = self._client.get_rescale_plan(
                    RendezvousName.TRAINING, self._config.node_rank,
                    outcome.round,
                )
            except Exception as e:
                logger.warning("rescale settle poll failed: %s", e)
                return False
            if aborted:
                logger.info(
                    "rescale plan %s aborted (round %s stale); falling "
                    "back to full restart", plan.plan_id, plan.new_round,
                )
                return False
            if still.exists and still.plan_id != plan.plan_id:
                # Superseded by a newer transition mid-apply.
                plan = still
                continue
            if not still.exists:
                # The plan settled between the two reads above — but an
                # ABORT also makes it disappear, and the stale check ran
                # first, so re-read it before trusting "completed".
                try:
                    if self._client.world_stale(
                        RendezvousName.TRAINING, plan.new_round
                    ):
                        logger.info(
                            "rescale plan %s aborted as it settled; "
                            "falling back to full restart", plan.plan_id,
                        )
                        return False
                except Exception as e:
                    logger.warning("rescale settle re-check failed: %s", e)
                    return False
                # Settled and the new round is live: transition done.
                outcome.adopt(plan.new_round, plan.new_world)
                logger.info(
                    "in-place rescale complete: now round %s, %s nodes, "
                    "world size %s", outcome.round, outcome.num_nodes,
                    outcome.world_size,
                )
                return True
            self._stopped.wait(interval)
        return False

    def _stop_workers(self, timeout: float = 15.0):
        for p in self._workers:
            if p.poll() is None:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        deadline = time.monotonic() + timeout
        for p in self._workers:
            remaining = max(0.1, deadline - time.monotonic())
            try:
                p.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
                p.wait()
        self._workers = []

    def stop(self):
        self._stopped.set()
        for attr in ("_heartbeat_task", "_resource_monitor",
                     "_training_monitor", "_config_tuner", "_link_probe",
                     "_preempt_watcher", "_shard_broker"):
            task = getattr(self, attr, None)
            if task is not None:
                task.stop()
        self._stop_workers()
        fs = getattr(self, "_forkserver", None)
        if fs is not None:
            fs.stop()
        # Drain the event-forwarding buffer so the master's timeline
        # gets this agent's final events before the process exits.
        flush_events()


def launch_agent(config: ElasticLaunchConfig, entrypoint: str,
                 args: List[str]) -> int:
    """Entry used by the CLI (parity: training.py:655)."""
    spec = WorkerSpec(entrypoint, args)
    client = MasterClient.singleton_instance()
    set_identity(config.node_rank, "agent")
    agent = ElasticTrainingAgent(config, spec, client)

    def _on_sigterm(signum, frame):
        logger.info("agent received signal %s; flushing checkpoint", signum)
        agent._save_shm_to_storage()
        agent.stop()
        sys.exit(143)

    signal.signal(signal.SIGTERM, _on_sigterm)
    try:
        return agent.run()
    finally:
        agent.stop()
