"""The typed env-var registry: every ``DLROVER_TPU_*`` knob, declared once.

Before this registry the package had 71 scattered ``os.getenv`` reads
across 24 files, each hand-rolling its own default and coercion — a
typo'd name silently read the default forever, and two sites could
disagree about what the default even was. Now:

- every variable is declared here exactly once with a name, type,
  default, and doc string;
- every other module references the registry constant (``ENV.FOO.get()``
  to read, ``ENV.FOO.name`` when exporting into a child environment);
- dtlint rule **DT006** rejects any ``DLROVER_TPU_*`` string literal
  outside this module, so an undeclared name cannot ship;
- the table in docs/configuration.md is *generated* from these
  declarations (``python -m tools.dtlint --env-table``) and a tier-1
  test fails when it drifts.

Reads go to ``os.environ`` at call time (not import time) — the agent
mutates the environment for spawned workers, and tests monkeypatch
freely.
"""

import os
from typing import Dict, List, Optional

_UNSET = object()

_TRUTHY = ("1", "true", "yes", "on")


class EnvVar:
    """One declared variable. ``get()`` returns the typed value, the
    declared default when unset, or the caller's override default."""

    __slots__ = ("name", "kind", "default", "doc")

    def __init__(self, name: str, kind: str, default, doc: str):
        self.name = name
        self.kind = kind
        self.default = default
        self.doc = doc

    def raw(self) -> Optional[str]:
        return os.environ.get(self.name)

    def is_set(self) -> bool:
        return self.name in os.environ

    def get(self, default=_UNSET):
        fallback = self.default if default is _UNSET else default
        raw = os.environ.get(self.name)
        if raw is None:
            return fallback
        if self.kind in ("str", "path"):
            return raw
        if self.kind == "bool":
            return raw.strip().lower() in _TRUTHY
        try:
            if self.kind == "int":
                return int(float(raw)) if "." in raw else int(raw)
            if self.kind == "float":
                return float(raw)
        except (TypeError, ValueError):
            return fallback
        return raw  # pragma: no cover - unknown kind, declared types only

    def set_in(self, env: Dict[str, str], value) -> None:
        """Export into a child-process environment mapping."""
        env[self.name] = str(value)

    def __repr__(self):
        return f"EnvVar({self.name}, {self.kind}, default={self.default!r})"


class EnvRegistry:
    def __init__(self):
        self._vars: Dict[str, EnvVar] = {}

    def _declare(self, name: str, kind: str, default, doc: str) -> EnvVar:
        if not doc:
            raise ValueError(f"env var {name} declared without a doc string")
        if name in self._vars:
            raise ValueError(f"env var {name} declared twice")
        var = EnvVar(name, kind, default, doc)
        self._vars[name] = var
        return var

    def str(self, name: str, default: str = "", doc: str = "") -> EnvVar:
        return self._declare(name, "str", default, doc)

    def path(self, name: str, default: str = "", doc: str = "") -> EnvVar:
        return self._declare(name, "path", default, doc)

    def int(self, name: str, default: int = 0, doc: str = "") -> EnvVar:
        return self._declare(name, "int", default, doc)

    def float(self, name: str, default: float = 0.0, doc: str = "") -> EnvVar:
        return self._declare(name, "float", default, doc)

    def bool(self, name: str, default: bool = False, doc: str = "") -> EnvVar:
        return self._declare(name, "bool", default, doc)

    def names(self) -> List[str]:
        return sorted(self._vars)

    def all(self) -> List[EnvVar]:
        return [self._vars[n] for n in sorted(self._vars)]

    def lookup(self, name: str) -> Optional[EnvVar]:
        return self._vars.get(name)


ENV = EnvRegistry()

# ---------------- identity / launch contract ----------------
JOB_NAME = ENV.str(
    "DLROVER_TPU_JOB_NAME", "local-job",
    "Job name; namespaces shm segments, unix sockets, and event identity.")
MASTER_ADDR = ENV.str(
    "DLROVER_TPU_MASTER_ADDR", "",
    "host:port of the job master; empty = no master (local run).")
NODE_ID = ENV.int(
    "DLROVER_TPU_NODE_ID", 0,
    "Stable node id assigned by the launcher (master-side identity).")
NODE_RANK = ENV.int(
    "DLROVER_TPU_NODE_RANK", 0,
    "Rendezvous rank of this node; defaults to the node id.")
NODE_NUM = ENV.int(
    "DLROVER_TPU_NODE_NUM", 1,
    "Number of nodes the job was launched with.")
COORDINATOR_ADDR = ENV.str(
    "DLROVER_TPU_COORDINATOR_ADDR", "",
    "host:port of the JAX distributed coordinator, exported by the agent "
    "for jax.distributed.initialize.")
PROCESS_ID = ENV.int(
    "DLROVER_TPU_PROCESS_ID", 0,
    "This worker's process index in the JAX distributed world.")
NUM_PROCESSES = ENV.int(
    "DLROVER_TPU_NUM_PROCESSES", 1,
    "Total process count in the JAX distributed world.")
LOCAL_RANK = ENV.int(
    "DLROVER_TPU_LOCAL_RANK", 0,
    "Worker index on this host.")
LOCAL_WORLD_SIZE = ENV.int(
    "DLROVER_TPU_LOCAL_WORLD_SIZE", 1,
    "Worker processes per host.")
RESTART_COUNT = ENV.int(
    "DLROVER_TPU_RESTART_COUNT", 0,
    "How many times the agent has restarted this worker.")
HOST_IP = ENV.str(
    "DLROVER_TPU_HOST_IP", "127.0.0.1",
    "Address other nodes can reach this host at (coordinator binding).")
SPAWN_TS = ENV.float(
    "DLROVER_TPU_SPAWN_TS", 0.0,
    "time.time() stamped by the agent at worker spawn; startup_s in "
    "worker boot metrics is measured from it.")

# ---------------- paths / runtime files ----------------
RUNTIME_DIR = ENV.path(
    "DLROVER_TPU_RUNTIME_DIR", "/tmp/dlrover_tpu",
    "Root of the host-local agent<->trainer runtime file contract.")
RUNTIME_METRICS_PATH = ENV.path(
    "DLROVER_TPU_RUNTIME_METRICS_PATH", "",
    "Override for the runtime-metrics JSON the trainer drops for the "
    "agent's config tuner.")
PARAL_CONFIG_PATH = ENV.path(
    "DLROVER_TPU_PARAL_CONFIG_PATH", "",
    "Override for the auto-parallelism config JSON the tuner writes.")
SOCK_DIR = ENV.path(
    "DLROVER_TPU_SOCK_DIR", "/tmp/dlrover_tpu/sock",
    "Directory for per-job unix sockets (shm coordination).")
SHM_DIR = ENV.path(
    "DLROVER_TPU_SHM_DIR", "/dev/shm",
    "Backing directory for flash-checkpoint shared-memory segments.")
TRACE_FILE = ENV.path(
    "DLROVER_TPU_TRACE_FILE", "",
    "When set, the launcher/agent's Tracer exports a Chrome trace here "
    "atomically at exit (and on demand), and each worker appends its "
    "events, a step's at a time, to <path minus .json>"
    ".worker<local_rank>.<restart_count>.jsonl.")
GOODPUT_JSON = ENV.path(
    "DLROVER_TPU_GOODPUT_JSON", "",
    "When set, the master writes its goodput-ledger summary JSON here "
    "on stop.")
LOG_LEVEL = ENV.str(
    "DLROVER_TPU_LOG_LEVEL", "INFO",
    "Python logging level for every process of the job.")

# ---------------- master / control plane ----------------
METRICS_PORT = ENV.int(
    "DLROVER_TPU_METRICS_PORT", -1,
    "Port for the master's Prometheus /metrics exporter; 0 = ephemeral, "
    "unset = exporter off.")
WAL_SYNC = ENV.str(
    "DLROVER_TPU_WAL_SYNC", "group",
    "State-store journal durability policy: 'group' (default) batches "
    "fsyncs across concurrent mutations via a dedicated commit thread "
    "(callers block on their batch's durability barrier), 'always' "
    "fsyncs once per mutation (the per-mutation baseline arm), 'none' "
    "never fsyncs the journal (page-cache durability only, the pre-"
    "group-commit legacy behavior).")
WAL_GROUP_WINDOW_S = ENV.float(
    "DLROVER_TPU_WAL_GROUP_WINDOW_S", 0.002,
    "Group-commit accumulation window: the commit thread waits this "
    "long after the first pending record before fsyncing, so one fsync "
    "covers every mutation that landed meanwhile. Bounds the extra "
    "latency a journaled RPC pays for durability; 0 fsyncs immediately "
    "(batching then comes only from records landing during the "
    "previous fsync).")
RPC_DEDUP_SIZE = ENV.int(
    "DLROVER_TPU_RPC_DEDUP_SIZE", 65536,
    "Entries the master's RPC dedup cache remembers. Must exceed the "
    "requests the whole fleet can have in retry flight at once: an "
    "evicted id makes a client retry re-apply a mutating message, so "
    "size it ~= agents x in-flight-RPCs-per-agent with headroom.")
RPC_DEDUP_TTL_S = ENV.float(
    "DLROVER_TPU_RPC_DEDUP_TTL_S", 0.0,
    "Seconds a dedup entry outlives its request. 0 (default) derives "
    "retry_deadline + request_timeout from the transport constants — "
    "strictly longer than any client can still be retrying. Only "
    "lower it in tests.")
RPC_WORKERS = ENV.int(
    "DLROVER_TPU_RPC_WORKERS", 16,
    "Bulk-lane handler threads in the master's RPC server (telemetry: "
    "beats, event batches, step/resource reports). The selector accept "
    "loop multiplexes all connections; this bounds concurrent handler "
    "execution instead of thread-per-connection.")
RPC_CONTROL_WORKERS = ENV.int(
    "DLROVER_TPU_RPC_CONTROL_WORKERS", 4,
    "Control-lane handler threads reserved for rendezvous / rescale / "
    "failure / kv / task RPCs, so a telemetry storm saturating the "
    "bulk lane can never starve the calls that re-form the world.")
RPC_DRAIN_S = ENV.float(
    "DLROVER_TPU_RPC_DRAIN_S", 5.0,
    "Seconds RpcServer.stop() waits for in-flight handlers to finish "
    "and their responses to flush before severing connections, so a "
    "graceful master stop under load doesn't leak half-applied socket "
    "errors into client retries.")
AGENT_BEAT = ENV.bool(
    "DLROVER_TPU_AGENT_BEAT", True,
    "Coalesce the agent's periodic node heartbeat, newest training "
    "step, and link-probe sample into one AgentBeat RPC per interval "
    "(one RPC per agent per tick instead of three). 0/false/off sends "
    "the legacy separate NodeHeartbeat/GlobalStep/probe-event RPCs.")
EVENT_SHED_PCT = ENV.float(
    "DLROVER_TPU_EVENT_SHED_PCT", 75.0,
    "Client-side backpressure: when the agent/worker event buffer is "
    "fuller than this percentage, ring-only telemetry events (step "
    "phases, probe samples, metric.*) are shed at emit time so "
    "incident events keep their buffer space. 100 disables shedding.")
EVENT_SHED_BACKLOG = ENV.int(
    "DLROVER_TPU_EVENT_SHED_BACKLOG", 64,
    "Master-side backpressure: when the RPC bulk lane has more than "
    "this many requests queued, the EventReport handler drops the "
    "ring-only telemetry kinds from incoming batches (incident events "
    "always land) so a telemetry storm can't starve rendezvous or "
    "rescale RPCs.")
STATE_SNAPSHOT_SECS = ENV.float(
    "DLROVER_TPU_STATE_SNAPSHOT_SECS", 30.0,
    "Seconds between periodic master state-store snapshots (journal "
    "rotation).")
STATE_SNAPSHOT_RECORDS = ENV.int(
    "DLROVER_TPU_STATE_SNAPSHOT_RECORDS", 2048,
    "Journal-record backstop forcing a snapshot between the periodic "
    "ones. A snapshot quiesces every mutation shard while it pickles "
    "the task table, so at lease data-plane rates (each grant/report "
    "is one record) the default can convoy the whole plane — raise it "
    "for shard-heavy jobs; replay time is the trade.")
SHARD_TIMEOUT = ENV.float(
    "DLROVER_TPU_SHARD_TIMEOUT", 300.0,
    "Seconds a dispatched data shard may stay unacked before the master "
    "reclaims it into todo.")
SHARD_LEASE_SHARDS = ENV.int(
    "DLROVER_TPU_SHARD_LEASE_SHARDS", 256,
    "Default shards per bulk lease grant (LeaseRequest.max_shards=0 "
    "falls back to it). Sized so one grant RPC covers seconds of a "
    "host's consumption; the 1/lease + 1/batch RPC amortization is the "
    "whole point of the lease plane.")
SHARD_LEASE_TTL_S = ENV.float(
    "DLROVER_TPU_SHARD_LEASE_TTL_S", 300.0,
    "Lease time-to-live: a lease not renewed (any LeaseReport renews) "
    "within this window is expired wholesale — every still-outstanding "
    "shard re-enters todo under fresh ids, exactly the doing-timeout "
    "contract at lease granularity.")
SHARD_LEASE_BATCH = ENV.int(
    "DLROVER_TPU_SHARD_LEASE_BATCH", 256,
    "Completion ids the agent broker buffers before flushing a "
    "LeaseReport to the master (the batch threshold; the flush "
    "interval below bounds latency when consumption is slow).")
SHARD_LEASE_FLUSH_S = ENV.float(
    "DLROVER_TPU_SHARD_LEASE_FLUSH_S", 2.0,
    "Max seconds the agent broker may hold buffered shard completions "
    "before flushing them, batch full or not — the beat-cadence bound "
    "on how much re-training a broker crash can cost.")
SHARD_LEASE_PLANE = ENV.str(
    "DLROVER_TPU_SHARD_LEASE_PLANE", "",
    "Name of the shm shard plane workers attach to. Exported by an "
    "agent running a shard-lease broker; when set, ShardingClient "
    "fetches shards and reports completions over shm with zero master "
    "RPCs in steady state. Empty = legacy per-call RPC path.")
SHARD_LEASE_PLANE_MB = ENV.int(
    "DLROVER_TPU_SHARD_LEASE_PLANE_MB", 4,
    "Size of the shm shard-plane segment in MiB (fetch ring + "
    "completion ring).")
SHARD_LEASE_LOW_WATER = ENV.int(
    "DLROVER_TPU_SHARD_LEASE_LOW_WATER", 128,
    "The agent broker requests a fresh lease when the shards it holds "
    "locally (sub-leased but unacked) drop below this count.")
SHARD_LEASE_READAHEAD = ENV.int(
    "DLROVER_TPU_SHARD_LEASE_READAHEAD", 0,
    "Shards the dataloader's readahead cache preloads ahead of "
    "consumption (keyed by shard id); 0 disables readahead.")
SHARD_LEASE_MIX_POLL_S = ENV.float(
    "DLROVER_TPU_SHARD_LEASE_MIX_POLL_S", 5.0,
    "Seconds between mixture-weight refreshes from the master kv store "
    "(the live-tunable weighted-sampling knob of the data plane).")
HANG_DETECTION_SECS = ENV.float(
    "DLROVER_TPU_HANG_DETECTION_SECS", 1800.0,
    "No step progress for this long marks the job hung.")
HEARTBEAT_TIMEOUT = ENV.float(
    "DLROVER_TPU_HEARTBEAT_TIMEOUT", 60.0,
    "Agent heartbeat silence after which the master declares the node "
    "dead.")
NODE_MONITOR_INTERVAL = ENV.float(
    "DLROVER_TPU_NODE_MONITOR_INTERVAL", 2.0,
    "Master-side node-liveness sweep interval.")
DEVICE_CHECK_TIMEOUT = ENV.float(
    "DLROVER_TPU_DEVICE_CHECK_TIMEOUT", 300.0,
    "Wall-clock budget for a whole device-check rendezvous round.")
AUTO_PARAL = ENV.bool(
    "DLROVER_TPU_AUTO_PARAL", False,
    "Opt-in: master pushes tuned dataloader configs to workers.")

# ---------------- worker / training ----------------
PROGRESS_EVERY = ENV.int(
    "DLROVER_TPU_PROGRESS_EVERY", 20,
    "Steps between step.progress event ranges from the trainer.")
PEAK_FLOPS = ENV.float(
    "DLROVER_TPU_PEAK_FLOPS", 0.0,
    "Override for the device peak FLOP/s used in MFU math when the "
    "device kind is unknown.")
FORKSERVER = ENV.bool(
    "DLROVER_TPU_FORKSERVER", True,
    "Spawn workers from the preloaded forkserver template (fast "
    "restarts); 0/false/off disables.")

# ---------------- checkpoint I/O ----------------
CKPT_STRIPE_MB = ENV.float(
    "DLROVER_TPU_CKPT_STRIPE_MB", 32.0,
    "Stripe size for parallel checkpoint I/O; 0 = legacy per-block "
    "format; clamped to >= 1 MB otherwise.")
CKPT_INCREMENTAL = ENV.bool(
    "DLROVER_TPU_CKPT_INCREMENTAL", True,
    "Content-hash incremental stripes: a stripe whose crc is unchanged "
    "since the previous committed step is recorded as a reference to "
    "that step's bin instead of rewritten; 0/false/off rewrites every "
    "byte each step.")
COPY_THREADS = ENV.int(
    "DLROVER_TPU_COPY_THREADS", 8,
    "Worker threads in the fastcopy pool (checksum + memcpy pipeline).")
DISABLE_NATIVE_COPY = ENV.bool(
    "DLROVER_TPU_DISABLE_NATIVE_COPY", False,
    "Force the Python fallback for fastcopy even when the native op "
    "builds.")
DISABLE_NATIVE = ENV.bool(
    "DLROVER_TPU_DISABLE_NATIVE", False,
    "Turn every native op builder off (pure-Python fallbacks).")

# ---------------- device check ----------------
CHECK_RESULT_PATH = ENV.path(
    "DLROVER_TPU_CHECK_RESULT_PATH", "",
    "File the device-check exercise writes its result JSON to "
    "(atomically) for the agent to read back.")
CHECK_MATMUL_SIZE = ENV.int(
    "DLROVER_TPU_CHECK_MATMUL_SIZE", 1024,
    "Square matmul size exercised per chip by the device check.")
CHECK_ALLGATHER_ROUNDS = ENV.int(
    "DLROVER_TPU_CHECK_ALLGATHER_ROUNDS", 10,
    "All-gather repetitions in the device-check collective exercise.")
CHECK_EXERCISE_TIMEOUT = ENV.float(
    "DLROVER_TPU_CHECK_EXERCISE_TIMEOUT", 60.0,
    "Seconds one device-check exercise process may run before the node "
    "(or its partner) is called faulty.")

# ---------------- live rescale plane ----------------
RESCALE = ENV.bool(
    "DLROVER_TPU_RESCALE", True,
    "Enable the in-place rescale plane: on a membership change with a "
    "surviving quorum the master issues a RescalePlan instead of letting "
    "the fleet restart. 0/false/off forces the legacy full-restart path.")
RESCALE_MIN_QUORUM = ENV.float(
    "DLROVER_TPU_RESCALE_MIN_QUORUM", 0.5,
    "Minimum surviving fraction of the old world required to rescale in "
    "place; below it the transition falls back to a full restart.")
RESCALE_MAX_SNAPSHOT_LAG = ENV.int(
    "DLROVER_TPU_RESCALE_MAX_SNAPSHOT_LAG", 1,
    "Maximum steps the newest shm snapshot may trail the live step for "
    "grown/moved shards to hydrate from memory; staler aborts the plan.")
RESCALE_APPLY_TIMEOUT_S = ENV.float(
    "DLROVER_TPU_RESCALE_APPLY_TIMEOUT_S", 60.0,
    "Seconds the master waits for every survivor's RescaleAck before "
    "aborting the plan and invalidating the round (full-restart "
    "fallback).")
RESCALE_POLL_INTERVAL_S = ENV.float(
    "DLROVER_TPU_RESCALE_POLL_INTERVAL_S", 0.2,
    "Agent/worker poll interval for an active rescale plan after their "
    "round goes stale.")
RESCALE_RESHAPE = ENV.bool(
    "DLROVER_TPU_RESCALE_RESHAPE", True,
    "Enable elastic mesh reshape: on a membership change the master "
    "searches the surviving device world for the best ParallelSpec and "
    "embeds it in the plan; survivors rebuild their mesh in place and "
    "hydrate state d2d where old and new shard covers overlap. 0/false "
    "keeps plans DP-only (accumulation schedule changes only).")
RESCALE_RESHAPE_STICKINESS = ENV.float(
    "DLROVER_TPU_RESCALE_RESHAPE_STICKINESS", 0.05,
    "Fractional step-time slack within which the reshape search prefers "
    "the spec closest to the current mesh layout (fewest state-moving "
    "axis changes), so a transition that can keep its shape does.")

# ---------------- preemption plane ----------------
PREEMPT = ENV.bool(
    "DLROVER_TPU_PREEMPT", True,
    "Enable the preemption plane: the agent watches notice sources and "
    "reports a PreemptionNotice so the master can flush, hand off the "
    "checkpoint writer lease, and shrink in place before the kill lands. "
    "0/false/off falls back to the reactive detect+rescale path.")
PREEMPT_NOTICE_FILE = ENV.path(
    "DLROVER_TPU_PREEMPT_NOTICE_FILE", "",
    "Path the preemption watcher polls for a termination notice; the "
    "file appearing (any content; optional 'deadline=<unix_ts>' line) "
    "counts as a notice for this node. Empty disables the file source.")
PREEMPT_NOW = ENV.bool(
    "DLROVER_TPU_PREEMPT_NOW", False,
    "Env-flip notice source: flipping this to 1 in the agent's "
    "environment is treated as a preemption notice with the default "
    "grace window. Meant for drills and operator-initiated drains.")
PREEMPT_POLL_INTERVAL_S = ENV.float(
    "DLROVER_TPU_PREEMPT_POLL_INTERVAL_S", 1.0,
    "Seconds between preemption-watcher polls of the notice sources; "
    "small because the grace window is short. 0 disables the watcher.")
PREEMPT_GRACE_S = ENV.float(
    "DLROVER_TPU_PREEMPT_GRACE_S", 30.0,
    "Default grace window in seconds assumed when a notice source does "
    "not announce its own deadline (env flip, bare notice file).")
PREEMPT_FALSE_ALARM_S = ENV.float(
    "DLROVER_TPU_PREEMPT_FALSE_ALARM_S", 5.0,
    "Seconds past a notice's deadline the master waits before declaring "
    "a false alarm: the node is still alive, so the writer lease "
    "reverts and the notice cancels with no restart.")

# ---------------- link probe / straggler attribution ----------------
PROBE_INTERVAL = ENV.float(
    "DLROVER_TPU_PROBE_INTERVAL", 30.0,
    "Seconds between background agent link-probe samples (D2H/H2D "
    "bandwidth proxy + master RPC round-trip). 0 disables the probe.")
PROBE_MB = ENV.int(
    "DLROVER_TPU_PROBE_MB", 8,
    "Payload megabytes per link-probe bandwidth sample; small on "
    "purpose — the probe must stay off the hot path.")
STRAGGLER_PHASES = ENV.bool(
    "DLROVER_TPU_STRAGGLER_PHASES", True,
    "Emit per-step phase-breakdown events (step.phases) from the "
    "trainer; the master's straggler detector feeds on them.")
STRAGGLER_PHASE_EVERY = ENV.int(
    "DLROVER_TPU_STRAGGLER_PHASE_EVERY", 1,
    "Emit step.phases every N steps (rate limit for very fast steps).")
STRAGGLER_WINDOW = ENV.int(
    "DLROVER_TPU_STRAGGLER_WINDOW", 32,
    "Rolling per-worker sample window (phase vectors and probe "
    "samples) the straggler detector classifies over.")
STRAGGLER_RATIO = ENV.float(
    "DLROVER_TPU_STRAGGLER_RATIO", 2.0,
    "Outlier threshold: a worker whose recent phase time exceeds (or "
    "probe bandwidth falls below) baseline by this factor is an "
    "outlier candidate.")
STRAGGLER_SUSTAIN = ENV.int(
    "DLROVER_TPU_STRAGGLER_SUSTAIN", 3,
    "Consecutive outlier evaluations before a straggler incident "
    "opens (debounces one-off hiccups).")
STRAGGLER_EVICT = ENV.bool(
    "DLROVER_TPU_STRAGGLER_EVICT", False,
    "Evict a sustained straggler through the node-manager path once "
    "it outlives DLROVER_TPU_STRAGGLER_EVICT_AFTER. Off: the detector "
    "only surfaces the recommendation (event + metric).")
STRAGGLER_EVICT_AFTER = ENV.float(
    "DLROVER_TPU_STRAGGLER_EVICT_AFTER", 120.0,
    "Seconds a classified straggler may persist before the eviction "
    "recommendation (or eviction, if enabled) fires.")

# ---------------- communication plane (link-aware comms) ----------------
COMMS_PROFILE = ENV.bool(
    "DLROVER_TPU_COMMS_PROFILE", True,
    "Run the master-side LinkProfileAggregator: fold probe.link samples "
    "into the per-axis fleet link profile, publish it through the kv "
    "store, and export it as gauges. Off: probes still feed the "
    "straggler detector but nothing consumes them for comms decisions.")
COMMS_WINDOW = ENV.int(
    "DLROVER_TPU_COMMS_WINDOW", 16,
    "Rolling per-node sample window the link-profile aggregator folds "
    "bandwidth/rtt over (independent of the straggler window).")
COMMS_SATURATION_RATIO = ENV.float(
    "DLROVER_TPU_COMMS_SATURATION_RATIO", 0.5,
    "Saturation threshold: the fleet's recent host-link bandwidth "
    "falling below this fraction of its rolling baseline makes the "
    "link a saturation candidate.")
COMMS_SATURATION_SUSTAIN = ENV.int(
    "DLROVER_TPU_COMMS_SATURATION_SUSTAIN", 2,
    "Consecutive aggregator folds a saturation candidate must persist "
    "before the flag raises — and folds back under the (frozen) "
    "baseline before it clears. Hysteresis against flapping the "
    "governor on one slow probe.")
COMMS_PUBLISH_EVERY_S = ENV.float(
    "DLROVER_TPU_COMMS_PUBLISH_EVERY_S", 5.0,
    "Minimum seconds between kv-store publishes of the fleet link "
    "profile (the monitor loop ticks faster; publishing every tick "
    "would churn the WAL via the kv export).")
COMMS_GOVERNOR = ENV.bool(
    "DLROVER_TPU_COMMS_GOVERNOR", True,
    "Let workers consult the CommsGovernor: while the published profile "
    "marks the host link saturated, checkpoint D2H staging and deferred "
    "metric readback are pushed off the hot path (bounded by "
    "DLROVER_TPU_COMMS_DEFER_MAX_STEPS).")
COMMS_GOVERNOR_REFRESH_S = ENV.float(
    "DLROVER_TPU_COMMS_GOVERNOR_REFRESH_S", 5.0,
    "Seconds between worker-side refreshes of the kv-published link "
    "profile (one small kv get; never on the step critical path).")
COMMS_DEFER_MAX_STEPS = ENV.int(
    "DLROVER_TPU_COMMS_DEFER_MAX_STEPS", 8,
    "Maximum consecutive steps the governor may defer a memory-snapshot "
    "staging (or metric readback) while the link stays saturated; after "
    "the cap the work runs anyway so crash-recovery lag stays bounded.")
COMMS_OVERLAP = ENV.bool(
    "DLROVER_TPU_COMMS_OVERLAP", True,
    "Backward-overlap kill switch: bucket gradient reduction into the "
    "accumulation scan (reduce-scatter per microbatch, last-bucket-only "
    "sync) when the spec's collective strategy asks for it. Off: the "
    "serialized accumulate-then-sync step, the A/B baseline.")

# ---------------- automatic straggler remediation ----------------
REMEDIATION = ENV.bool(
    "DLROVER_TPU_REMEDIATION", True,
    "Drive straggler verdicts through the automatic remediation policy "
    "(master/remediation.py): quarantine via in-place shrink, probation "
    "regrow on probe recovery, permanent eviction after repeated "
    "probation failures. Off: verdicts stay observe-only (PR-10 "
    "behavior).")
REMEDIATION_SUSTAIN_TICKS = ENV.int(
    "DLROVER_TPU_REMEDIATION_SUSTAIN_TICKS", 3,
    "Policy ticks a detector verdict must persist (SUSPECT state) "
    "before quarantine — hysteresis on top of the detector's own "
    "sustain, so a flapping verdict never moves the world.")
REMEDIATION_COOLDOWN_S = ENV.float(
    "DLROVER_TPU_REMEDIATION_COOLDOWN_S", 30.0,
    "Minimum seconds between remediation actions, fleet-wide. Bounds "
    "the world-change rate no matter how many nodes degrade at once.")
REMEDIATION_MAX_CONCURRENT = ENV.int(
    "DLROVER_TPU_REMEDIATION_MAX_CONCURRENT", 1,
    "Maximum nodes simultaneously quarantined or on probation. A wider "
    "outage than this is a fleet problem, not a straggler problem — "
    "the policy holds instead of shrinking the job away.")
REMEDIATION_MIN_WORLD = ENV.int(
    "DLROVER_TPU_REMEDIATION_MIN_WORLD", 2,
    "Never quarantine below this many nodes (on top of the rescale "
    "plane's own survivor-quorum check).")
REMEDIATION_PROBATION_S = ENV.float(
    "DLROVER_TPU_REMEDIATION_PROBATION_S", 60.0,
    "Seconds a recovered node must stay clean after regrow before its "
    "record clears back to HEALTHY.")
REMEDIATION_BACKOFF_S = ENV.float(
    "DLROVER_TPU_REMEDIATION_BACKOFF_S", 60.0,
    "Base backoff after a nacked/declined quarantine or a failed "
    "probation, doubling per failure, before the node is eligible for "
    "another action.")
REMEDIATION_PROBATION_FAILS = ENV.int(
    "DLROVER_TPU_REMEDIATION_PROBATION_FAILS", 2,
    "Probation failures (verdict returning after a regrow) before the "
    "node is permanently evicted through the node-manager path.")

# ---------------- brain decision layer ----------------
BRAIN = ENV.bool(
    "DLROVER_TPU_BRAIN", False,
    "Run the brain decision layer (brain/policy.py) off the master "
    "monitor loop: history-driven start configuration plus a goodput "
    "policy that grows the world while tokens/s still scales and "
    "shrinks chips whose marginal contribution goes negative. Off "
    "(default, the --auto-tunning analogue is opt-in): the planes stay "
    "purely reactive and joins grow the world unconditionally.")
BRAIN_SUSTAIN_TICKS = ENV.int(
    "DLROVER_TPU_BRAIN_SUSTAIN_TICKS", 3,
    "Policy ticks a grow/shrink signal must persist before the brain "
    "acts — hysteresis so a noisy throughput sample never moves the "
    "world.")
BRAIN_COOLDOWN_S = ENV.float(
    "DLROVER_TPU_BRAIN_COOLDOWN_S", 60.0,
    "Minimum seconds between brain actions. The cooldown is FLEET-wide "
    "and shared with the remediation policy: a remediation quarantine "
    "arms it for the brain and a brain action arms it for remediation, "
    "so the two policies never fight over the same world.")
BRAIN_MIN_WORLD = ENV.int(
    "DLROVER_TPU_BRAIN_MIN_WORLD", 2,
    "The brain never shrinks the world below this many nodes, on top "
    "of the rescale plane's survivor-quorum pre-flight.")
BRAIN_GROW_EFFICIENCY = ENV.float(
    "DLROVER_TPU_BRAIN_GROW_EFFICIENCY", 0.5,
    "Keep growing while each added node delivered at least this "
    "fraction of linear throughput scaling; below it the last grow is "
    "judged not worth its chips and the target stops rising.")
BRAIN_SHRINK_DRAG_PCT = ENV.float(
    "DLROVER_TPU_BRAIN_SHRINK_DRAG_PCT", 12.5,
    "Shrink a node out when its drag on the collective exceeds this "
    "percent of the median step time — the point where one straggling "
    "chip costs more wall clock than its 1/N compute contributes "
    "(marginal goodput per chip goes negative at 100/world_size).")
BRAIN_SAVE_INTERVAL_S = ENV.float(
    "DLROVER_TPU_BRAIN_SAVE_INTERVAL_S", 30.0,
    "Seconds between fsyncs of the brain metrics store's append-only "
    "log (and between periodic compactions when the log outgrows its "
    "retention window). Durability window for brain history, not "
    "correctness: records are crc-framed and a torn tail drops clean.")
BRAIN_HISTORY = ENV.int(
    "DLROVER_TPU_BRAIN_HISTORY", 2048,
    "Metrics records retained per job in the brain store; the "
    "append-only log compacts down to this many when it grows past "
    "four times the cap.")

# ---------------- master high availability ----------------
MASTER_HA_DIR = ENV.path(
    "DLROVER_TPU_MASTER_HA_DIR", "",
    "Shared coordination directory for master hot standby: holds the "
    "primacy lease record, the fleet-wide incarnation counter, and the "
    "published endpoint file. Unset = HA off (single master, external "
    "relaunch as before). Must be reachable by primary and standby "
    "(same filesystem).")
MASTER_HA_LEASE_TTL_S = ENV.float(
    "DLROVER_TPU_MASTER_HA_LEASE_TTL_S", 3.0,
    "Primacy lease time-to-live. A standby may claim primacy once the "
    "recorded lease is older than this; the primary must renew well "
    "inside it (see DLROVER_TPU_MASTER_HA_RENEW_S).")
MASTER_HA_RENEW_S = ENV.float(
    "DLROVER_TPU_MASTER_HA_RENEW_S", 1.0,
    "Seconds between primacy-lease renewals by the holder. Keep at "
    "most TTL/3 so one missed renewal (GC pause, slow fsync) does not "
    "forfeit primacy.")
MASTER_HA_POLL_S = ENV.float(
    "DLROVER_TPU_MASTER_HA_POLL_S", 0.5,
    "Standby cadence: seconds between WAL subscribe pulls and lease "
    "observations. Bounds both replication lag and failover detection "
    "latency.")
MASTER_HA_SEGMENT_BYTES = ENV.int(
    "DLROVER_TPU_MASTER_HA_SEGMENT_BYTES", 1 << 20,
    "Maximum bytes of durable WAL shipped per WalSegment response. "
    "Caps per-pull memory on both ends; a lagging standby just pulls "
    "again immediately.")
MASTER_HA_CLAIM_STALE_S = ENV.float(
    "DLROVER_TPU_MASTER_HA_CLAIM_STALE_S", 10.0,
    "Age after which an orphaned promotion claim file (a contender "
    "that died between claim and lease write) is swept so later "
    "contenders are not deadlocked.")
MASTER_HA_ENDPOINT_FILE = ENV.path(
    "DLROVER_TPU_MASTER_HA_ENDPOINT_FILE", "",
    "File the active master publishes its host:port endpoint to and "
    "RpcClient re-reads between retry rounds (endpoint re-resolution). "
    "Defaults to <MASTER_HA_DIR>/endpoint when HA is on; may also be "
    "set alone to ride an externally relaunched master onto a new "
    "port without process restarts.")

# ---------------- fault injection / debug ----------------
CHAOS = ENV.str(
    "DLROVER_TPU_CHAOS", "",
    "Fault plan: inline JSON or @/path/to/plan.json; unset = chaos off. "
    "Inherited by every process of the job.")
CHAOS_LOG = ENV.path(
    "DLROVER_TPU_CHAOS_LOG", "",
    "Journal of fired chaos events (one JSON line each) for "
    "reproducibility drills.")
LOCKDEP = ENV.bool(
    "DLROVER_TPU_LOCKDEP", False,
    "Arm the runtime lock-order detector: instrumented locks record the "
    "acquisition graph and fail fast on a cycle. Debug-only; plain "
    "threading locks (zero overhead) when unset.")
LOCKDEP_EXPORT = ENV.path(
    "DLROVER_TPU_LOCKDEP_EXPORT", "",
    "Write the recorded lock-order graph as JSON here at master stop "
    "(lockdep.export_graph). dtlint DT010 merges the artifact with its "
    "static graph so drill-observed orders join the cycle check.")
MOCK_ERR_RANK = ENV.int(
    "DLROVER_TPU_MOCK_ERR_RANK", -1,
    "Test knob: node rank that fails its device check.")
MOCK_STRAGGLER_RANK = ENV.int(
    "DLROVER_TPU_MOCK_STRAGGLER_RANK", -1,
    "Test knob: node rank that straggles in the device check.")
MOCK_STRAGGLER_SECS = ENV.float(
    "DLROVER_TPU_MOCK_STRAGGLER_SECS", 3.0,
    "Test knob: how long the mock straggler sleeps.")


# ---------------- typed helpers (NodeEnv contract) ----------------


def get_node_id() -> int:
    return NODE_ID.get()


def get_node_rank() -> int:
    return NODE_RANK.get(default=get_node_id())


def get_node_num() -> int:
    return NODE_NUM.get()


def get_process_id() -> int:
    return PROCESS_ID.get()


def get_num_processes() -> int:
    return NUM_PROCESSES.get()


def get_local_rank() -> int:
    return LOCAL_RANK.get()


def get_local_world_size() -> int:
    return LOCAL_WORLD_SIZE.get()


def get_job_name() -> str:
    return JOB_NAME.get()


def get_master_addr() -> str:
    return MASTER_ADDR.get()
