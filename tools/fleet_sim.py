"""Synthetic fleet harness: hammer a REAL master with thousands of agents.

Control-plane scale testing without 10k hosts: one in-process
:class:`JobMaster` (real ``RpcServer``, real ``MasterServicer``, real
``MasterStateStore`` WAL) takes traffic from N connection threads, each
multiplexing a slice of M simulated agents over its own ``RpcClient``
— the same persistent-connection transport real agents use, so framing,
dedup, incarnation stamping and the servicer's lane split are all
exercised, not mocked.

Traffic mix per simulated agent "tick" (mirrors a live agent's steady
state): one coalesced :class:`AgentBeat` (heartbeat + step + probe
sample) always; a journaled kv-store set/get pair every ``kv_every``
ticks; an :class:`EventReport` batch (telemetry + lifecycle kinds)
every ``events_every`` ticks; a shard ``TaskRequest``/``TaskReport``
round-trip every ``task_every`` ticks. The journaled fraction is what
makes the WAL arms comparable: ``fsyncs_per_mutation`` comes straight
from ``MasterStateStore.wal_status()``.

Used by ``tests/test_master_scale.py`` (a smoke run at ~100 agents and
group-commit vs per-mutation-fsync arms at 2,000), ``run_lease_fleet``
by ``tests/test_data_plane.py`` and ``run_brain_drill`` by
``tests/test_brain_policy.py``. Run standalone::

    python -m tools.fleet_sim --agents 1000 --duration 5
"""

import argparse
import json
import multiprocessing
import os
import shutil
import tempfile
import threading
import time
from typing import Dict, List, Optional

from dlrover_tpu.common import env_utils
from dlrover_tpu.common import messages as m
from dlrover_tpu.common.rpc import RpcClient
from dlrover_tpu.observability.events import JobEvent


def _raise_nofile(target: int = 65536):
    """Best-effort RLIMIT_NOFILE bump: every connection thread holds a
    socket and the master holds the peer end, plus the WAL/snapshot
    files — the default 1024 soft limit trips first on big fleets."""
    try:
        import resource

        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        if soft < target:
            resource.setrlimit(
                resource.RLIMIT_NOFILE, (min(target, hard), hard)
            )
    except (ImportError, ValueError, OSError):
        pass


def _percentile(samples: List[float], p: float) -> float:
    if not samples:
        return 0.0
    samples = sorted(samples)
    idx = min(len(samples) - 1, int(p / 100.0 * len(samples)))
    return samples[idx]


class _AgentSlice(threading.Thread):
    """One connection thread driving a slice of simulated agents.

    Real deployments give every agent its own connection; at harness
    scale the bottleneck under test is the MASTER (its selector loop,
    worker lanes, locks and WAL), so multiplexing agents over a few
    hundred client threads keeps the load generator cheap while the
    master still sees the full agent population (distinct node_ids,
    full heartbeat registry, full dedup traffic).
    """

    def __init__(self, addr: str, agent_ids: List[int], deadline: float,
                 kv_every: int, events_every: int, task_every: int,
                 dataset: str, event_batch: int):
        super().__init__(daemon=True, name=f"fleet-{agent_ids[0]}")
        self._client = RpcClient(addr, timeout=30.0, retry_deadline=10.0)
        self._ids = agent_ids
        self._deadline = deadline
        self._kv_every = kv_every
        self._events_every = events_every
        self._task_every = task_every
        self._dataset = dataset
        self._event_batch = event_batch
        self.latencies: List[float] = []
        self.beats = 0
        self.errors = 0
        self.beaten: Dict[int, int] = {}

    def _call(self, req) -> bool:
        t0 = time.perf_counter()
        try:
            self._client.call(req)
        except Exception:
            self.errors += 1
            return False
        self.latencies.append(time.perf_counter() - t0)
        return True

    def run(self):
        tick = 0
        probe = {"h2d_mbps": 900.0, "d2h_mbps": 850.0, "rtt_ms": 1.2}
        while time.monotonic() < self._deadline:
            tick += 1
            for aid in self._ids:
                if time.monotonic() >= self._deadline:
                    break
                now = time.time()
                # Phase every agent's extra work by its id: real fleets
                # don't fire 10k kv writes on the same clock edge, and
                # aligned bursts would measure the harness's own queueing,
                # not the master's steady-state latency.
                if self._call(m.AgentBeat(
                    node_id=aid, node_type="worker", timestamp=now,
                    step=tick, step_ts=now,
                    probe=probe if (tick + aid) % 3 == 0 else {},
                )):
                    self.beats += 1
                    self.beaten[aid] = self.beaten.get(aid, 0) + 1
                if self._kv_every and (tick + aid) % self._kv_every == 0:
                    self._call(m.KVStoreSet(
                        node_id=aid, key=f"fleet/{aid}",
                        value=str(tick).encode(),
                    ))
                    self._call(m.KVStoreGet(node_id=aid, key=f"fleet/{aid}"))
                if self._events_every and (tick + aid) % self._events_every == 0:
                    events = [
                        JobEvent(
                            kind="metric.cpu_percent", ts=now, node_id=aid,
                            role="agent", pid=0, args={"value": 42.0},
                        )
                        for _ in range(self._event_batch - 1)
                    ]
                    events.append(JobEvent(
                        kind="node.heartbeat_tick", ts=now, node_id=aid,
                        role="agent", pid=0, args={"tick": tick},
                    ))
                    self._call(m.EventReport(node_id=aid, events=events))
                if self._task_every and (tick + aid) % self._task_every == 0:
                    t0 = time.perf_counter()
                    try:
                        task = self._client.call(m.TaskRequest(
                            node_id=aid, dataset_name=self._dataset,
                        ))
                    except Exception:
                        self.errors += 1
                        continue
                    self.latencies.append(time.perf_counter() - t0)
                    if task is not None and task.exists:
                        self._call(m.TaskReport(
                            node_id=aid, dataset_name=self._dataset,
                            task_id=task.task_id, success=True,
                        ))
        self._client.close()


def run_fleet(agents: int = 1000, duration_s: float = 5.0,
              conns: int = 32, wal_sync: Optional[str] = None,
              state_dir: str = "", kv_every: int = 4,
              events_every: int = 8, task_every: int = 0,
              event_batch: int = 8,
              group_window_s: Optional[float] = None,
              control_workers: Optional[int] = None) -> Dict:
    """Run the fleet against a fresh in-process master; return metrics.

    ``wal_sync`` pins ``DLROVER_TPU_WAL_SYNC`` for the master's store
    ("group" vs "always" — the two bench arms); ``group_window_s``
    likewise pins the accumulation window. ``control_workers`` sizes
    the control-lane pool: a journaled RPC parks its worker in the
    group-commit durability wait (~the accumulation window), so the
    lane needs roughly ``conns`` workers for the waits to overlap
    instead of queueing — waiting workers sleep on a condvar and cost
    no GIL. All overrides are restored on exit; they must span
    ``prepare()`` too, because the RpcServer reads its pool sizes when
    it starts there.
    """
    _raise_nofile()
    from dlrover_tpu.master.master import JobMaster

    conns = max(1, min(conns, agents))
    tmp = ""
    if not state_dir:
        tmp = state_dir = tempfile.mkdtemp(prefix="fleet_sim_")
    overrides = {}
    if wal_sync is not None:
        overrides[env_utils.WAL_SYNC.name] = wal_sync
    if group_window_s is not None:
        overrides[env_utils.WAL_GROUP_WINDOW_S.name] = repr(group_window_s)
    if control_workers is not None:
        overrides[env_utils.RPC_CONTROL_WORKERS.name] = str(control_workers)
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        master = JobMaster(
            port=0, node_num=agents, job_name="fleet-sim",
            state_dir=state_dir,
        )
        master.prepare()  # starts the RpcServer + node-monitor loop
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    addr = master.addr
    dataset = "fleet-shards"
    try:
        admin = RpcClient(addr, timeout=30.0, retry_deadline=10.0)
        if task_every:
            admin.call(m.DatasetShardParams(
                node_id=0, dataset_name=dataset,
                dataset_size=10_000_000, shard_size=1000, num_epochs=1,
            ))
        deadline = time.monotonic() + duration_s
        ids = list(range(agents))
        slices = [
            _AgentSlice(
                addr, ids[i::conns], deadline, kv_every, events_every,
                task_every, dataset, event_batch,
            )
            for i in range(conns)
        ]
        t0 = time.monotonic()
        for s in slices:
            s.start()
        for s in slices:
            s.join(timeout=duration_s + 60.0)
        elapsed = time.monotonic() - t0

        latencies = [x for s in slices for x in s.latencies]
        beats = sum(s.beats for s in slices)
        errors = sum(s.errors for s in slices)
        beaten: Dict[int, int] = {}
        for s in slices:
            for aid, n in s.beaten.items():
                beaten[aid] = beaten.get(aid, 0) + n
        # "Sustained" = the agent completed at least two beat intervals
        # during the window — it registered AND kept reporting.
        sustained = sum(1 for n in beaten.values() if n >= 2)
        wal = master.state_store.wal_status()
        mutations = max(1, wal["appended_records"])
        plane = master.observability
        out = {
            "agents": agents,
            "agents_sustained": sustained,
            "conns": conns,
            "duration_s": round(elapsed, 2),
            "rpcs": len(latencies),
            "rpc_errors": errors,
            "beats_per_s": round(beats / max(elapsed, 1e-9), 1),
            "rpc_p50_ms": round(_percentile(latencies, 50) * 1e3, 3),
            "rpc_p99_ms": round(_percentile(latencies, 99) * 1e3, 3),
            "rpc_max_ms": round(max(latencies) * 1e3, 3) if latencies else 0.0,
            "rpc_over_1s": sum(1 for x in latencies if x > 1.0),
            "server_rpc_p99_ms": round(
                max(
                    [
                        plane.rpc_hist.percentile(labels["type"], 99.0)
                        for labels, _ in plane.rpc_hist.samples()
                    ] or [0.0],
                ) * 1e3, 3,
            ),
            "wal_policy": wal["policy"],
            "wal_mutations": wal["appended_records"],
            "wal_fsyncs": wal["fsync_count"],
            "fsyncs_per_mutation": round(wal["fsync_count"] / mutations, 4),
            "events_shed": plane.shed_events,
        }
        return out
    finally:
        master.stop()
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)


class _LeaseSlice(threading.Thread):
    """One connection thread driving a slice of data-plane workers.

    ``mode="lease"``: each worker takes a bulk :class:`m.LeaseRequest`
    (timed — that RPC is the only fetch-side tail a plane worker ever
    waits on; ring pops are microseconds) and acks it back in
    ``completion_batch``-sized :class:`m.LeaseReport` chunks — the
    broker's steady-state traffic shape, minus the shm hop.

    ``mode="per_call"``: the pre-lease baseline, one
    ``TaskRequest``/``TaskReport`` pair per shard (2 RPCs/shard).
    """

    def __init__(self, addr: str, worker_ids: List[int], deadline: float,
                 dataset: str, shards_per_lease: int,
                 completion_batch: int, mode: str):
        super().__init__(daemon=True, name=f"lease-{worker_ids[0]}")
        self._client = RpcClient(addr, timeout=60.0, retry_deadline=20.0)
        self._ids = worker_ids
        self._deadline = deadline
        self._dataset = dataset
        self._spl = shards_per_lease
        self._batch = completion_batch
        self._mode = mode
        self.fetch_lat: List[float] = []
        self.completions = 0
        self.leases = 0
        self.rpcs = 0
        self.errors = 0

    def run(self):
        try:
            if self._mode == "per_call":
                self._run_per_call()
            else:
                self._run_lease()
        finally:
            self._client.close()

    def _run_per_call(self):
        while time.monotonic() < self._deadline:
            for wid in self._ids:
                if time.monotonic() >= self._deadline:
                    return
                t0 = time.perf_counter()
                try:
                    task = self._client.call(m.TaskRequest(
                        node_id=wid, dataset_name=self._dataset,
                    ))
                except Exception:
                    self.errors += 1
                    continue
                self.fetch_lat.append(time.perf_counter() - t0)
                self.rpcs += 1
                if task is None or not task.exists:
                    return  # dataset drained
                try:
                    self._client.call(m.TaskReport(
                        node_id=wid, dataset_name=self._dataset,
                        task_id=task.task_id, success=True,
                    ))
                    self.rpcs += 1
                    self.completions += 1
                except Exception:
                    self.errors += 1

    def _run_lease(self):
        while time.monotonic() < self._deadline:
            for wid in self._ids:
                if time.monotonic() >= self._deadline:
                    return
                t0 = time.perf_counter()
                try:
                    lease = self._client.call(m.LeaseRequest(
                        node_id=wid, dataset_name=self._dataset,
                        max_shards=self._spl,
                    ))
                except Exception:
                    self.errors += 1
                    continue
                self.fetch_lat.append(time.perf_counter() - t0)
                self.rpcs += 1
                if lease is None or not lease.exists:
                    if lease is not None and lease.finished:
                        return
                    time.sleep(0.05)
                    continue
                self.leases += 1
                ids = [t.task_id for t in lease.tasks]
                for i in range(0, len(ids), self._batch):
                    chunk = ids[i:i + self._batch]
                    try:
                        self._client.call(m.LeaseReport(
                            node_id=wid, dataset_name=self._dataset,
                            lease_id=lease.lease_id, done_ids=chunk,
                        ))
                        self.rpcs += 1
                        self.completions += len(chunk)
                    except Exception:
                        self.errors += 1


def _proc_main(addr: str, worker_ids: List[int], conns: int,
               duration_s: float, deadline_wall: float, dataset: str,
               shards_per_lease: int, completion_batch: int, mode: str,
               out_q):
    """Child-process entry (spawn context): drive a slice of the fleet
    from OUTSIDE the master's GIL and ship summarized stats back.

    Runs for ``duration_s`` from its own start (spawn/import time never
    counts against the measured window) but never past ``deadline_wall``
    — a straggler child must not stretch the fleet's tail."""
    _raise_nofile()
    start = time.time()
    duration = max(0.1, min(duration_s, deadline_wall - start))
    deadline = time.monotonic() + duration
    conns = max(1, min(conns, len(worker_ids)))
    slices = [
        _LeaseSlice(
            addr, worker_ids[i::conns], deadline, dataset,
            shards_per_lease, completion_batch, mode,
        )
        for i in range(conns)
    ]
    for s in slices:
        s.start()
    for s in slices:
        s.join(timeout=duration + 60.0)
    lat = sorted(x for s in slices for x in s.fetch_lat)
    step = max(1, len(lat) // 2000)
    out_q.put({
        "start": start,
        "end": time.time(),
        # Percentiles survive decimation of a SORTED sample list; 2k
        # points per child keeps the queue payload small at any scale.
        "fetch_lat": lat[::step] + lat[-1:],
        "completions": sum(s.completions for s in slices),
        "leases": sum(s.leases for s in slices),
        "rpcs": sum(s.rpcs for s in slices),
        "errors": sum(s.errors for s in slices),
    })


def run_lease_fleet(workers: int = 200, duration_s: float = 5.0,
                    procs: int = 4, conns_per_proc: int = 8,
                    shards_per_lease: int = 512,
                    completion_batch: int = 512,
                    mode: str = "lease",
                    dataset_size: int = 1_000_000, shard_size: int = 1,
                    num_epochs: int = 4,
                    state_dir: str = "",
                    wal_sync: Optional[str] = "group") -> Dict:
    """Data-plane load run: a real in-process master fed by ``procs``
    child PROCESSES (the PR-11 single-process generator tops out around
    4k RPC/s on its own GIL — far below the plane's throughput).

    Returns the BENCH ``data_plane`` metrics: ``completions_per_s``,
    ``leases_per_s``, ``master_rpcs_per_shard``, ``fetch_p99_ms``.
    """
    _raise_nofile()
    from dlrover_tpu.master.master import JobMaster

    tmp = ""
    if not state_dir:
        tmp = state_dir = tempfile.mkdtemp(prefix="lease_fleet_")
    overrides = {
        # Snapshots pickle the whole task table under the mutation-shard
        # quiesce; mid-bench that is a multi-second master stall
        # measuring the snapshotter, not the data plane (both the timer
        # AND the record backstop would fire — every grant/report is a
        # journal record). Journal replay covers durability meanwhile.
        env_utils.STATE_SNAPSHOT_SECS.name: "3600",
        env_utils.STATE_SNAPSHOT_RECORDS.name: "10000000",
    }
    if wal_sync is not None:
        overrides[env_utils.WAL_SYNC.name] = wal_sync
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        master = JobMaster(
            port=0, node_num=workers, job_name="lease-fleet",
            state_dir=state_dir,
        )
        master.prepare()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    addr = master.addr
    dataset = "lease-shards"
    try:
        admin = RpcClient(addr, timeout=30.0, retry_deadline=10.0)
        admin.call(m.DatasetShardParams(
            node_id=0, dataset_name=dataset, dataset_size=dataset_size,
            shard_size=shard_size, num_epochs=num_epochs,
        ))
        # Warm the split: epoch creation is lazy (first fetch triggers
        # it) and at bench sizes takes seconds under the tasks shard —
        # every child's opening grant would queue behind it and the
        # p99 would measure the splitter, not the plane.
        warm = admin.call(m.LeaseRequest(
            node_id=0, dataset_name=dataset, max_shards=1,
        ))
        if warm is not None and warm.exists:
            admin.call(m.LeaseReport(
                node_id=0, dataset_name=dataset, lease_id=warm.lease_id,
                done_ids=[], failed_ids=[t.task_id for t in warm.tasks],
                release=True,
            ))
        admin.close()
        procs = max(1, procs)
        ctx = multiprocessing.get_context("spawn")
        out_q = ctx.Queue()
        ids = list(range(workers))
        # Generous lead time: spawned children re-import the package
        # before their clocks start.
        deadline_wall = time.time() + duration_s + 2.0 * procs
        children = [
            ctx.Process(
                target=_proc_main,
                args=(addr, ids[i::procs], conns_per_proc, duration_s,
                      deadline_wall, dataset, shards_per_lease,
                      completion_batch, mode, out_q),
                daemon=True,
            )
            for i in range(procs)
        ]
        for c in children:
            c.start()
        results = []
        for _ in children:
            results.append(out_q.get(timeout=duration_s + 120.0))
        for c in children:
            c.join(timeout=30.0)
        window = max(r["end"] for r in results) - min(
            r["start"] for r in results
        )
        completions = sum(r["completions"] for r in results)
        leases = sum(r["leases"] for r in results)
        rpcs = sum(r["rpcs"] for r in results)
        lat = [x for r in results for x in r["fetch_lat"]]
        wal = master.state_store.wal_status()
        return {
            "mode": mode,
            "workers": workers,
            "procs": procs,
            "duration_s": round(window, 2),
            "completions": completions,
            "completions_per_s": round(completions / max(window, 1e-9), 1),
            "leases": leases,
            "leases_per_s": round(leases / max(window, 1e-9), 1),
            "master_rpcs": rpcs,
            "master_rpcs_per_shard": round(rpcs / max(completions, 1), 4),
            "fetch_p50_ms": round(_percentile(lat, 50) * 1e3, 3),
            "fetch_p99_ms": round(_percentile(lat, 99) * 1e3, 3),
            "rpc_errors": sum(r["errors"] for r in results),
            "wal_mutations": wal["appended_records"],
            "wal_fsyncs": wal["fsync_count"],
        }
    finally:
        master.stop()
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)


#: Healthy lockstep throughput (steps/s) by world size — a deliberate
#: scaling knee at 3: the 4th chip buys ~2% (collective cost eats the
#: gain), which is exactly the shape the brain's marginal test and the
#: autoconf knee walk exist to find.
_BRAIN_PERF = {1: 55.0, 2: 100.0, 3: 145.0, 4: 148.0}
#: Step-time multiplier while the chronically degraded node is in the
#: world: a synchronous collective steps at the slowest member's pace.
_BRAIN_DRAG = 1.5
#: Per-step phase profiles fed to the straggler detector; the degraded
#: node's compute drag (~46% over the fleet median) sits ABOVE the
#: brain's shrink threshold but BELOW the remediation verdict ratio —
#: the regime the brain exists for.
_PHASES_OK = {"input_s": 0.01, "compute_s": 0.10,
              "collective_s": 0.01, "readback_s": 0.01}
_PHASES_DEGRADED = {"input_s": 0.01, "compute_s": 0.16,
                    "collective_s": 0.01, "readback_s": 0.01}


def _seed_brain_history(path: str, job_name: str):
    """Pre-seed the cross-job metrics store with prior-run throughput:
    the observed curve replaces the analytic guess at every world the
    history has seen, so the start recommendation lands on the knee."""
    from dlrover_tpu.brain.autoconf import WORLD_PERF_KIND
    from dlrover_tpu.brain.store import BrainMetricsStore

    store = BrainMetricsStore(path)
    for world, speed in _BRAIN_PERF.items():
        for i in range(3):
            store.append(job_name, {
                "kind": WORLD_PERF_KIND, "ts": float(i),
                "world_size": world, "samples_per_s": speed,
            })
    store.close()


def run_brain_drill(ticks: int = 40, nodes: int = 4,
                    degraded_node: int = 3, arm: str = "brain",
                    state_dir: str = "", tick_s: float = 2.0) -> Dict:
    """The ISSUE-19 acceptance drill: a job starts at the WRONG world
    size (all ``nodes`` chips, one chronically degraded) and the brain
    must converge it — recommendation from seeded cross-job history,
    oversize/drag shrink parking the degraded node, every decision a
    journaled ``("brain", ...)`` record reproduced exactly once by a
    relaunched master.

    Three arms share one throughput model (``_BRAIN_PERF`` paced by the
    slowest member) so ``tests/test_brain_policy.py`` can compare them:

    - ``brain``      — starts at ``nodes``, policy on. Must end at the
      searched-best world (3) with the degraded node parked, and the
      relaunched master must replay to the same decision state.
    - ``static_wrong`` — starts at ``nodes``, policy off: the degraded
      node paces the oversized world forever.
    - ``oracle_start`` — starts at the searched-best size but with the
      degraded node aboard, and never adapts: right size, wrong member.
    """
    from dlrover_tpu.common.constants import RendezvousName
    from dlrover_tpu.master.master import JobMaster

    job_name = "brain-drill"
    tmp = ""
    if not state_dir:
        tmp = state_dir = tempfile.mkdtemp(prefix="brain_drill_")
    brain_on = arm == "brain"
    if arm == "oracle_start":
        start_ranks = sorted(
            [degraded_node]
            + [r for r in range(nodes) if r != degraded_node][:2]
        )
    else:
        start_ranks = list(range(nodes))
    overrides = {
        env_utils.BRAIN.name: "1" if brain_on else "0",
        env_utils.BRAIN_SUSTAIN_TICKS.name: "2",
        env_utils.BRAIN_COOLDOWN_S.name: "0",
        env_utils.BRAIN_MIN_WORLD.name: "2",
        env_utils.RESCALE.name: "1",
        # The drill isolates the brain: remediation stays quiet (the
        # injected drag is below its verdict ratio anyway).
        env_utils.REMEDIATION.name: "0",
    }
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    master = master2 = None
    try:
        if brain_on:
            _seed_brain_history(
                os.path.join(state_dir, "brain_metrics.log"), job_name
            )
        master = JobMaster(
            port=0, node_num=len(start_ranks), job_name=job_name,
            state_dir=state_dir,
        )
        TRAIN = RendezvousName.TRAINING
        mgr = master.rdzv_managers[TRAIN]
        for r in start_ranks:
            master.servicer.handle(m.JoinRendezvous(
                node_id=r, node_rank=r, local_world_size=1,
                rdzv_name=TRAIN,
            ))
        mgr.get_comm_world(start_ranks[0])
        spec = {"data": len(start_ranks), "fsdp": 1, "tensor": 1,
                "seq": 1, "expert": 1, "pipe": 1, "zero": False}
        for r in start_ranks:
            extra = {"rescale_capable": True}
            if r == start_ranks[0]:
                extra.update({
                    "global_batch": 32, "micro_batch": 8,
                    "model_profile": {"param_count": 100_000_000},
                    "hbm": 16e9, "parallel_spec": spec,
                })
            master.servicer.handle(m.ModelInfo(
                node_id=r, params_count=100_000_000, batch_size=32,
                extra=extra,
            ))

        sim_now = time.time()
        step = 0
        last_n = 0
        sim_steps = sim_time = 0.0
        rate = 0.0
        converged_at = -1
        timeline = []
        for tick in range(ticks):
            world = mgr.current_world()
            n = len(world)
            if n != last_n:
                # A trainer restarts its step clock across a world
                # change; stale-window samples would smear two worlds'
                # speeds into one reading.
                master.speed_monitor.reset_running_speed_monitor()
                last_n = n
            degraded_in = degraded_node in world
            rate = _BRAIN_PERF.get(n, 0.0) / (
                _BRAIN_DRAG if degraded_in else 1.0
            )
            sim_now += tick_s
            sim_steps += rate * tick_s
            sim_time += tick_s
            step += max(1, int(rate * tick_s))
            if world:
                master.speed_monitor.collect_global_step(
                    step, sim_now, worker_id=min(world)
                )
            for w in world:
                master.straggler_detector.note_phases(
                    w,
                    dict(_PHASES_DEGRADED if w == degraded_node
                         else _PHASES_OK),
                    step=step,
                )
            master.straggler_detector.tick()
            master.brain.tick(now=sim_now)
            pending = master.brain.status()["pending"]
            if pending["plan_id"] >= 0:
                # Stand in for the survivors' agents: ack the issued
                # shrink plan through the journaled RescaleAck RPC so
                # plan outcomes replay on the relaunched master.
                for r in sorted(mgr.current_world()):
                    master.servicer.handle(m.RescaleAck(
                        node_id=r, plan_id=pending["plan_id"],
                        node_rank=r, ok=True,
                    ))
            if brain_on:
                # Shrunk-out (and never-admitted) nodes keep polling
                # the join path — the brain's park gate is what holds
                # them out, and a release lifts it with no new RPC.
                for r in range(nodes):
                    if r not in mgr.current_world():
                        master.servicer.handle(m.JoinRendezvous(
                            node_id=r, node_rank=r, local_world_size=1,
                            rdzv_name=TRAIN,
                        ))
            world = mgr.current_world()
            if not timeline or timeline[-1][1:] != (
                len(world), degraded_node in world
            ):
                timeline.append(
                    (tick, len(world), degraded_node in world)
                )
            if (
                converged_at < 0 and len(world) == 3
                and degraded_node not in world
            ):
                converged_at = tick

        end_world = mgr.current_world()
        status = master.brain.status()
        out = {
            "arm": arm,
            "ticks": ticks,
            "world_start": len(start_ranks),
            "world_end": len(end_world),
            "degraded_node": degraded_node,
            "degraded_in_world": degraded_node in end_world,
            "degraded_parked": str(degraded_node) in status["parked"],
            "target": status["target"],
            "recommendation": {
                k: status["recommendation"].get(k)
                for k in ("world_size", "source", "feasible")
            } if status["recommendation"] else {},
            "actions": status["actions"],
            "deferrals": status["deferrals"],
            "samples_per_s_avg": round(sim_steps / max(sim_time, 1e-9), 1),
            "samples_per_s_final": round(rate, 1),
            "converged_at_tick": converged_at,
            "timeline": timeline,
        }

        if brain_on:
            # ---- failover half: crash (no graceful snapshot) and
            # relaunch on the same state dir; the ("brain", ...) WAL
            # records must reproduce the decision state exactly once.
            pre = master.brain.checkpoint()
            from dlrover_tpu.observability.events import uninstall_sink

            master._stopped.set()
            master._server.stop()
            uninstall_sink(master._event_sink_fn)
            if master.brain_store is not None:
                master.brain_store.close()
            master.state_store.close()
            master2 = JobMaster(
                port=0, node_num=len(start_ranks), job_name=job_name,
                state_dir=state_dir,
            )
            post = master2.brain.checkpoint()
            replay_match = (
                post["target"] == pre["target"]
                and post["parked"] == pre["parked"]
                and post["recommendation"] == pre["recommendation"]
                and post["actions"].get("shrink", 0)
                == pre["actions"].get("shrink", 0)
            )
            # The replayed shrink re-marks its plan pending; the acks
            # replayed through their rpc records settle it on the first
            # tick (exactly once — never a re-shrink).
            world2 = master2.rdzv_managers[TRAIN].current_world()
            master2.brain.tick(now=sim_now + tick_s)
            post_tick = master2.brain.status()
            out.update({
                "replay_match": replay_match,
                "replay_world": len(world2),
                "replay_degraded_in_world": degraded_node in world2,
                "replay_pending_cleared":
                    post_tick["pending"]["plan_id"] < 0,
                "replay_target": post["target"],
            })
        return out
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if master2 is not None:
            master2.stop()
        elif master is not None:
            master.stop()
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--agents", type=int, default=1000)
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--conns", type=int, default=32)
    ap.add_argument("--wal-sync", default=None,
                    choices=(None, "group", "always", "none"))
    ap.add_argument("--kv-every", type=int, default=4)
    ap.add_argument("--events-every", type=int, default=8)
    ap.add_argument("--task-every", type=int, default=0)
    ap.add_argument("--procs", type=int, default=0,
                    help="data-plane mode: N child processes of lease "
                         "workers instead of the control-plane mix")
    ap.add_argument("--workers", type=int, default=200)
    ap.add_argument("--mode", default="lease",
                    choices=("lease", "per_call"))
    ap.add_argument("--shards-per-lease", type=int, default=512)
    ap.add_argument("--completion-batch", type=int, default=512)
    ap.add_argument("--brain-drill", default="",
                    choices=("", "brain", "static_wrong", "oracle_start"),
                    help="run the brain auto-scaling drill arm instead "
                         "of the load mix")
    ap.add_argument("--ticks", type=int, default=40)
    args = ap.parse_args(argv)
    if args.brain_drill:
        out = run_brain_drill(ticks=args.ticks, arm=args.brain_drill)
        print(json.dumps(out, sort_keys=True))
        return 0
    if args.procs > 0:
        out = run_lease_fleet(
            workers=args.workers, duration_s=args.duration,
            procs=args.procs, mode=args.mode,
            shards_per_lease=args.shards_per_lease,
            completion_batch=args.completion_batch,
            wal_sync=args.wal_sync,
        )
        print(json.dumps(out, sort_keys=True))
        return 0
    out = run_fleet(
        agents=args.agents, duration_s=args.duration, conns=args.conns,
        wal_sync=args.wal_sync, kv_every=args.kv_every,
        events_every=args.events_every, task_every=args.task_every,
    )
    print(json.dumps(out, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
