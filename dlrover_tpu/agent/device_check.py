"""Pre-flight device/ICI check (agent side).

Capability parity with the reference's ``NetworkCheckElasticAgent``
(``elastic_agent/torch/training.py:767-906``): before training starts, the
agent joins the master's device-check rendezvous, the master pairs nodes
into small groups, and every group runs a timed collective + matmul
exercise in a spawned process (:mod:`dlrover_tpu.agent.run_device_check`).
Results go back to the master, whose
:class:`~dlrover_tpu.master.rendezvous.DeviceCheckRendezvousManager`
localizes fault nodes by re-pairing suspects with known-good nodes in a
second round, and flags stragglers by the elapsed-time median×2 rule.

TPU specifics: the exercise runs JAX collectives (over ICI on real chips,
over the CPU backend in tests) instead of NCCL allgathers; a hung or dead
partner surfaces as an exercise-process timeout, which is exactly the
failure signature of a sick chip or link.
"""

import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, Optional, Tuple

from dlrover_tpu.chaos.injector import fault_hit
from dlrover_tpu.chaos.sites import ChaosSite
from dlrover_tpu.common import env_utils
from dlrover_tpu.common.backoff import ExponentialBackoff
from dlrover_tpu.common.constants import NodeEnv, RendezvousName
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.periodic import PeriodicTask
from dlrover_tpu.common.rpc import find_free_port
from dlrover_tpu.observability.events import EventKind, emit

_MAX_CHECK_ROUNDS = 3


def _exercise_timeout() -> float:
    # How long a single exercise process may run before we call the node
    # (or its partner) faulty. Tests shrink this via the environment.
    return env_utils.CHECK_EXERCISE_TIMEOUT.get()


def _setup_group_coordinator(client, round_: int, group: int,
                             world: Dict[int, int], node_rank: int) -> str:
    """The lowest rank of the check group hosts a JAX coordinator; the
    address is published through the master kv-store."""
    key = f"devcheck/{round_}/{group}"
    first = sorted(world)[0]
    if node_rank == first:
        host = env_utils.HOST_IP.get()
        addr = f"{host}:{find_free_port()}"
        client.kv_store_set(key, addr.encode())
        return addr
    return client.kv_store_wait([key], timeout=60.0)[key].decode()


def _run_exercise(config, client, round_: int, group: int,
                  world: Dict[int, int], node_rank: int) -> Tuple[bool, float]:
    """Spawn the check program for this group; returns (normal, elapsed)."""
    members = sorted(world)
    try:
        coordinator = _setup_group_coordinator(client, round_, group, world,
                                               node_rank)
    except TimeoutError:
        # The group leader died before publishing the coordinator address:
        # report a failed check instead of crashing the healthy agent.
        logger.error("device check: group %s coordinator never appeared",
                     group)
        return False, float("inf")
    result_path = tempfile.mktemp(prefix="dlrover_tpu_devcheck_")
    env = dict(os.environ)
    env.update({
        NodeEnv.JOB_NAME: config.job_name,
        NodeEnv.NODE_RANK: str(node_rank),
        NodeEnv.COORDINATOR_ADDR: coordinator,
        NodeEnv.PROCESS_ID: str(members.index(node_rank)),
        NodeEnv.NUM_PROCESSES: str(len(members)),
        env_utils.CHECK_RESULT_PATH.name: result_path,
    })
    cmd = [sys.executable, "-m", "dlrover_tpu.agent.run_device_check"]
    start = time.monotonic()
    timeout = _exercise_timeout()
    try:
        proc = subprocess.run(
            cmd, env=env, timeout=timeout,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        normal = proc.returncode == 0
        if not normal:
            logger.error(
                "device-check exercise failed (rc=%s):\n%s",
                proc.returncode, proc.stdout.decode(errors="replace")[-2000:],
            )
    except subprocess.TimeoutExpired:
        logger.error("device-check exercise timed out after %ss", timeout)
        normal = False
    elapsed = time.monotonic() - start
    if normal:
        try:
            with open(result_path) as f:
                elapsed = float(f.read().strip())
        except (ValueError, OSError):
            pass  # no/garbled result file: fall back to wall time
    try:
        os.unlink(result_path)
    except FileNotFoundError:
        pass
    return normal, elapsed


def run_device_check(config, client) -> bool:
    """Run check rounds until the diagnosis is done.

    Returns False when this node must not join training: it was confirmed
    faulty, or it is a straggler and ``--exclude-straggler`` is set.
    """
    node_rank = config.node_rank
    for check_round in range(_MAX_CHECK_ROUNDS):
        client.join_rendezvous(
            RendezvousName.DEVICE_CHECK, node_rank, config.nproc_per_node
        )
        # Wait for the master to freeze the round and hand us a group.
        deadline = time.monotonic() + config.rdzv_timeout
        world: Dict[int, int] = {}
        backoff = ExponentialBackoff(initial=0.1, max_delay=1.0)
        while time.monotonic() < deadline:
            round_, group, world = client.get_comm_world(
                RendezvousName.DEVICE_CHECK, node_rank
            )
            if world and node_rank in world:
                break
            backoff.sleep(deadline - time.monotonic())
        if not world:
            logger.warning("device check round never formed; skipping check")
            return True
        logger.info(
            "device check round %s: group %s members %s",
            round_, group, sorted(world),
        )
        normal, elapsed = _run_exercise(
            config, client, round_, group, world, node_rank
        )
        client.report_check_result(node_rank, normal, elapsed, round_=round_)

        # Poll the diagnosis: done -> act; suspects AND our round fully
        # reported -> another round; otherwise keep waiting for reports.
        poll_deadline = time.monotonic() + _exercise_timeout() + 60.0
        need_new_round = False
        backoff = ExponentialBackoff(initial=0.1, max_delay=1.0)
        while time.monotonic() < poll_deadline:
            fault_nodes, done, completed = client.get_fault_nodes()
            if done:
                stragglers, _, _ = client.get_stragglers()
                if node_rank in fault_nodes:
                    logger.error(
                        "device check: this node (%s) is a confirmed fault "
                        "node", node_rank,
                    )
                    return False
                if node_rank in stragglers:
                    logger.warning(
                        "device check: this node (%s) is a straggler "
                        "(exclude=%s)", node_rank, config.exclude_straggler,
                    )
                    if config.exclude_straggler:
                        return False
                logger.info(
                    "device check passed (fault=%s stragglers=%s)",
                    fault_nodes, stragglers,
                )
                return True
            if fault_nodes and completed >= round_:
                need_new_round = True
                break
            backoff.sleep(poll_deadline - time.monotonic())
        if not need_new_round:
            logger.warning("device-check diagnosis timed out; proceeding")
            return True
    logger.warning("device check inconclusive after %s rounds; proceeding",
                   _MAX_CHECK_ROUNDS)
    return True


# ---------------- continuous link probe ----------------


class LinkProbe:
    """Background link telemetry: the pre-flight check above answers
    "was the link sane at start" exactly once; this thread keeps
    answering it for the rest of the job.

    Every ``DLROVER_TPU_PROBE_INTERVAL`` seconds it samples, off the
    training hot path:

    - **H2D/D2H bandwidth proxy** — a small write+read through the shm
      staging directory, the same path checkpoint snapshots take. The
      agent never times a real host↔device transfer: the *workers* own
      the TPU runtime, and an agent-side client would take their chips.
    - **master RPC round-trip** — a read-only kv-store get, the
      cross-host control-link microbenchmark every agent can run.

    Samples go out as ``probe.link`` events (ring-only on the master —
    never journaled) for the straggler detector's per-worker link
    profile. The probe is rate-limited by construction and *pauses
    under checkpoint pressure*: while the saver has a persist round in
    flight — a periodic persist or the proactive preemption grace-window
    flush, both raise the same busy signal — the sample is skipped, so
    probe I/O never contends with checkpoint I/O on the same disks and
    links.

    The ``probe.link degrade`` chaos site scales measured bandwidth
    down (and inflates RTT) by ``args["factor"]`` — the deterministic
    link-degradation drill.
    """

    def __init__(self, client=None,
                 interval: Optional[float] = None,
                 payload_mb: Optional[int] = None,
                 busy_fn: Optional[Callable[[], bool]] = None,
                 sample_fn: Optional[Callable[[], Dict]] = None,
                 sink: Optional[Callable[[Dict], None]] = None):
        self._client = client
        self._interval = (
            interval if interval is not None
            else env_utils.PROBE_INTERVAL.get()
        )
        self._mb = max(1, payload_mb or env_utils.PROBE_MB.get())
        self._busy_fn = busy_fn or self._saver_busy
        self._sample_fn = sample_fn
        # Optional sample sink: with heartbeat coalescing on, the agent
        # collects samples here and folds the newest into its periodic
        # AgentBeat — the master synthesizes the probe.link event, so
        # emitting one here too would double-count.
        self._sink = sink
        self._seq = 0
        self.skipped = 0
        self._task: Optional[PeriodicTask] = None

    # Process-wide count of rescale/reshape d2d transfers in flight
    # (brackets around the agent's in-place transition window). The ckpt
    # saver raises its own busy signal; transition traffic moves through
    # the very same host links without one, so without this bracket a
    # sample taken mid-transfer would read as a degraded link and could
    # trip the fleet saturation flag on every reshape.
    _transfers = 0
    _transfers_lock = threading.Lock()

    @classmethod
    def transfer_window(cls):
        """Context manager marking a rescale/reshape d2d transfer in
        flight; probe samples taken inside are flagged ``transfer``
        (the master-side aggregator drops them from the baseline fold)."""
        import contextlib

        @contextlib.contextmanager
        def _window():
            with cls._transfers_lock:
                cls._transfers += 1
            try:
                yield
            finally:
                with cls._transfers_lock:
                    cls._transfers -= 1

        return _window()

    @classmethod
    def transfer_active(cls) -> bool:
        with cls._transfers_lock:
            return cls._transfers > 0

    @staticmethod
    def _saver_busy() -> bool:
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

        saver = AsyncCheckpointSaver.get_ckpt_saver()
        return bool(saver is not None and getattr(saver, "busy", False))

    def start(self):
        if self._interval <= 0:
            return
        self._task = PeriodicTask(
            self.sample_once, self._interval, name="link-probe"
        )
        self._task.start()

    def stop(self, join_timeout: float = 2.0):
        if self._task is not None:
            self._task.stop(join_timeout)
            self._task = None

    # ------------- one sample -------------
    def sample_once(self) -> Optional[Dict]:
        self._seq += 1
        try:
            if self._busy_fn():
                # Checkpoint persist in flight: stay off its disks/links.
                self.skipped += 1
                return None
        except Exception:  # dtlint: disable=DT001 -- a broken busy probe must not stop link telemetry
            pass
        transfer = self.transfer_active()
        sample = (
            self._sample_fn() if self._sample_fn is not None
            else self._measure()
        )
        if transfer:
            # Taken while a rescale/reshape d2d transfer held the link:
            # real traffic, not link health. Flag it so the aggregator
            # keeps it out of the saturation baseline; the straggler
            # detector still sees a sample (gap-free rings).
            sample["transfer"] = True
        chaos = fault_hit(ChaosSite.PROBE_LINK, detail=str(self._seq))
        if chaos is not None and chaos.kind == "degrade":
            factor = float(chaos.args.get("factor", 0.1)) or 0.1
            for key in ("h2d_mbps", "d2h_mbps"):
                if key in sample:
                    sample[key] *= factor
            if "rtt_ms" in sample:
                sample["rtt_ms"] /= factor
        if self._sink is not None:
            self._sink(dict(sample, seq=self._seq))
        else:
            emit(EventKind.PROBE_LINK, seq=self._seq, **sample)
        return sample

    def _measure(self) -> Dict:
        sample: Dict = {}
        sample.update(self._measure_shm())
        if self._client is not None:
            t0 = time.perf_counter()
            try:
                self._client.kv_store_get("__linkprobe__")
                sample["rtt_ms"] = round(
                    (time.perf_counter() - t0) * 1e3, 3
                )
            except Exception:  # dtlint: disable=DT001 -- master briefly down: the probe keeps sampling local links
                pass
        return sample

    def _measure_shm(self) -> Dict:
        """Write+read through the shm staging dir — the checkpoint D2H
        path proxy available to every agent without touching the TPU."""
        shm_dir = env_utils.SHM_DIR.get() or "/dev/shm"
        if not os.path.isdir(shm_dir):
            shm_dir = tempfile.gettempdir()
        path = os.path.join(
            shm_dir, f".dlrover_tpu_linkprobe_{os.getpid()}"
        )
        payload = os.urandom(1 << 20) * self._mb
        mb = len(payload) / 1e6
        try:
            t0 = time.perf_counter()
            with open(path, "wb") as f:
                f.write(payload)
                f.flush()
            t1 = time.perf_counter()
            with open(path, "rb") as f:
                f.read()
            t2 = time.perf_counter()
        except OSError as e:
            logger.warning("link probe shm sample failed: %s", e)
            return {}
        finally:
            try:
                os.unlink(path)
            except OSError:
                pass
        return {
            "h2d_mbps": round(mb / max(t1 - t0, 1e-9), 1),
            "d2h_mbps": round(mb / max(t2 - t1, 1e-9), 1),
        }
