"""The resume path on the tracer's spans: the agent's failure handling,
crash flush and restart, and a restore's phases, nested by ``parent`` and
one clock with ``last_restore_stats``."""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from dlrover_tpu.agent.agent import (
    ElasticLaunchConfig,
    ElasticTrainingAgent,
    WorkerSpec,
)
from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
from dlrover_tpu.common import ckpt_persist
from dlrover_tpu.common.ckpt_meta import ckpt_shm_name
from dlrover_tpu.common.shared_memory import SharedMemory
from dlrover_tpu.common.storage import PosixDiskStorage
from dlrover_tpu.train.checkpoint import CheckpointEngine
from dlrover_tpu.utils import tracing
from dlrover_tpu.utils.tracing import Tracer

from tests.test_profiler import _tiny_trainer


@pytest.fixture
def tracer(monkeypatch):
    """A fresh tracer behind ``get_tracer()`` for the call sites."""
    fresh = Tracer()
    monkeypatch.setattr(tracing, "_tracer", fresh)
    return fresh


def _spans(events) -> dict:
    return {e["args"]["id"]: e for e in events if e["ph"] == "X"}


def _one(spans: dict, name: str) -> dict:
    found = [e for e in spans.values() if e["name"] == name]
    assert len(found) == 1, (name, found)
    return found[0]


def _children(spans: dict, parent: dict) -> list:
    return sorted(
        (e for e in spans.values()
         if e["args"].get("parent") == parent["args"]["id"]),
        key=lambda e: e["args"]["id"],
    )


def _covered(spans: dict, parent: dict) -> float:
    """The share of ``parent`` its direct children cover."""
    return sum(e["dur"] for e in _children(spans, parent)) / parent["dur"]


# ------------------------------------------------------------ the agent

class _Client:
    """What the agent asks of the master on the failure path."""

    master_addr = "127.0.0.1:0"

    def __init__(self):
        self.failures = []

    def report_failure(self, message, level=None, restart_count=0):
        self.failures.append(message)

    def report_node_status(self, status, reason=""):
        pass

    def join_rendezvous(self, name, node_rank, local_world_size):
        pass

    def get_comm_world(self, name, node_rank):
        return 1, 0, {0: 1}

    def kv_store_set(self, key, value):
        pass


def _snapshot(job_name, tmp_path, step):
    """A memory snapshot the agent's saver holds (what a worker leaves
    behind at a kill)."""
    AsyncCheckpointSaver.start_async_saving_ckpt()
    ckpt_dir = str(tmp_path / "ckpts")
    engine = CheckpointEngine(ckpt_dir)
    assert engine.save_to_memory(step, {"w": jnp.arange(4096.0)})
    deadline = time.monotonic() + 10.0
    while AsyncCheckpointSaver.get_ckpt_saver() is None:
        assert time.monotonic() < deadline, "saver never registered"
        time.sleep(0.05)
    return engine, ckpt_dir


class TestAgentSpans:
    def test_a_failed_worker_is_flushed_and_restarted_under_spans(
        self, tracer, job_name, tmp_path, monkeypatch
    ):
        trace_file = str(tmp_path / "agent_trace.json")
        monkeypatch.setenv("DLROVER_TPU_TRACE_FILE", trace_file)
        monkeypatch.setenv("DLROVER_TPU_FORKSERVER", "0")
        script = tmp_path / "dies.py"
        script.write_text("import sys, time\ntime.sleep(0.3)\nsys.exit(3)\n")
        engine, ckpt_dir = _snapshot(job_name, tmp_path, 11)
        agent = ElasticTrainingAgent(
            ElasticLaunchConfig(monitor_interval=0.05, max_restarts=1),
            WorkerSpec(str(script), []), _Client(),
        )
        try:
            outcome = agent._rendezvous()
            agent._start_workers(outcome)
            assert agent._monitor_workers(outcome) == "failed"
            assert agent._restart("failed") is not None
        finally:
            agent._stop_workers()
            engine.close()
            AsyncCheckpointSaver.stop()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))
        assert ckpt_persist.read_tracker(PosixDiskStorage(), ckpt_dir) == 11

        spans = _spans(tracer.events)
        failure = _one(spans, "agent.failure")
        assert failure["args"]["cause"] == "crash"
        assert [list(c) for c in failure["args"]["codes"]] == [[0, 3]]
        assert failure["args"]["restart"] == 0
        # The exit lies between the last poll that saw the worker alive
        # and the poll that opened the span.
        assert failure["args"]["alive_ts"] * 1e6 < failure["ts"]
        assert [e["name"] for e in _children(spans, failure)] == [
            "agent.report_failure", "ckpt-crash-flush"
        ]
        flush = _one(spans, "ckpt-crash-flush")
        under_flush = [e["name"] for e in _children(spans, flush)]
        assert under_flush == [
            "ckpt.flush_scan", "ckpt.flush_lock_wait", "ckpt.flush_lock_wait",
            "ckpt.persist_shard", "ckpt.flush_commit",
        ]
        waits = [e for e in _children(spans, flush)
                 if e["name"] == "ckpt.flush_lock_wait"]
        assert [(e["args"]["lock"], e["args"]["acquired"]) for e in waits] == [
            ("flush", True), ("shard", True)
        ]
        persist = _one(spans, "ckpt.persist_shard")
        assert persist["args"]["bytes"] == persist["args"]["written"] > 0
        assert "checksum_s" in persist["args"]
        assert "ckpt.persist_write" in [
            e["name"] for e in _children(spans, persist)
        ]
        commit = _one(spans, "ckpt.flush_commit")
        assert [e["name"] for e in _children(spans, commit)] == ["ckpt.gc"]

        # The ring exported at the restart holds the failure, whole.
        with open(trace_file) as f:
            exported = _spans(json.load(f)["traceEvents"])
        for name in ("agent.failure", "agent.report_failure",
                     "ckpt-crash-flush", "ckpt.flush_scan",
                     "ckpt.flush_lock_wait", "ckpt.persist_shard",
                     "ckpt.flush_commit"):
            assert name in {e["name"] for e in exported.values()}
        assert exported[flush["args"]["id"]]["args"]["parent"] == (
            failure["args"]["id"])

        restart = _one(spans, "agent.restart")
        assert restart["args"]["reason"] == "failed"
        assert restart["args"]["restart"] == 1
        assert restart["ts"] >= failure["ts"] + failure["dur"]
        assert [e["name"] for e in _children(spans, restart)] == [
            "agent.stop_workers", "agent.trace_export", "rendezvous",
            "agent.start_workers",
        ]
        spawns = [e for e in spans.values() if e["name"] == "agent.spawn"]
        assert len(spawns) == 2     # the first start and the restart
        start = _one(
            {k: e for k, e in spans.items()
             if e["args"].get("parent") == restart["args"]["id"]},
            "agent.start_workers",
        )
        again = _children(spans, start)
        assert [e["name"] for e in again] == ["agent.spawn"]
        assert again[0]["args"]["local_rank"] == 0
        assert again[0]["args"]["pid"] > 0
        assert not [e for e in tracer.events if e["name"].startswith(
            "workers-")]

    def test_a_membership_change_flushes_under_a_failure_span(
        self, tracer, job_name, monkeypatch
    ):
        class Stale(_Client):
            def num_nodes_waiting(self, name):
                return 0

            def world_stale(self, name, round_):
                return True

        monkeypatch.setenv("DLROVER_TPU_RESCALE", "0")
        agent = ElasticTrainingAgent(
            ElasticLaunchConfig(monitor_interval=0.01),
            WorkerSpec(sys.executable, []), Stale(),
        )
        agent._workers = [_Alive()]
        assert agent._monitor_workers(agent._rendezvous()) == (
            "membership_changed"
        )
        failure = _one(_spans(tracer.events), "agent.failure")
        assert failure["args"]["cause"] == "stale_round"
        assert failure["args"]["codes"] is None
        assert failure["args"]["alive_ts"] * 1e6 <= failure["ts"]


class _Alive:
    pid = 0

    def poll(self):
        return None


# ---------------------------------------------------------- the restore

def _state(seed, sharded=None):
    """32 MB of parameters (the phases, not the fixed costs, are most of
    the restore) and, where given, a leaf sharded over the devices."""
    state = {
        "params": {"w": jnp.full((2048, 4096), float(seed), jnp.float32)},
        "step": seed,
    }
    if sharded is not None:
        state["params"]["s"] = jax.device_put(
            jnp.arange(64.0 * 8).reshape(64, 8) + seed, sharded
        )
    return state


RESTORE_PHASES = {"ckpt.restore_read": "read_s",
                  "ckpt.restore_verify": "verify_s",
                  "ckpt.restore_device_put": "device_put_s"}


class TestRestoreSpans:
    def _check_stats(self, spans, restore, stats):
        """Each stat is the seconds of its spans in the restoring attempt
        (these restores make one attempt, so every span of the name under
        ``ckpt.restore``)."""
        children = _children(spans, restore)
        for name, key in RESTORE_PHASES.items():
            found = [e["dur"] / 1e6 for e in children if e["name"] == name]
            assert stats[key] == pytest.approx(sum(found), rel=1e-9)
        assert stats["assemble_s"] == pytest.approx(
            stats["total_s"] - stats["read_s"] - stats["verify_s"]
            - stats["device_put_s"], abs=1e-12)

    def test_a_storage_restore_is_its_phases(self, tracer, job_name,
                                             tmp_path):
        mesh = Mesh(np.array(jax.devices()), ("x",))
        sharded = NamedSharding(mesh, PartitionSpec("x"))
        ckpt_dir = str(tmp_path / "ckpts")
        engine = CheckpointEngine(ckpt_dir)
        try:
            assert engine.save_to_storage(7, _state(3, sharded))
            loader = CheckpointEngine(ckpt_dir)
            step, restored = loader.load(_state(0, sharded))
        finally:
            engine.close()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))
        assert step == 7
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["s"]),
            np.asarray(_state(3, sharded)["params"]["s"]),
        )
        stats = loader.last_restore_stats
        assert stats["source"] == "storage" and stats["device_put_s"] > 0
        spans = _spans(tracer.events)
        restore = [e for e in spans.values() if e["name"] == "ckpt.restore"][-1]
        assert restore["args"]["source"] == "storage"
        assert restore["args"]["step"] == 7
        assert restore["args"]["bytes"] == stats["bytes"] > 32 * 2 ** 20
        names = [e["name"] for e in _children(spans, restore)]
        assert names == [
            "ckpt.restore_wait_staged", "ckpt.restore_locate",
            "ckpt.restore_locate", "ckpt.restore_locate",
            "ckpt.restore_locate", "ckpt.restore_verify",
            # the sharded leaf, filled and put before the next is filled
            "ckpt.restore_copy", "ckpt.restore_device_put",
            # the host leaves, their batched reads and the staged copy
            "ckpt.restore_copy", "ckpt.restore_read", "ckpt.restore_copy",
        ]
        self._check_stats(spans, restore, stats)
        assert _covered(spans, restore) >= 0.95

    def test_a_memory_restore_is_its_phases(self, tracer, job_name,
                                            tmp_path):
        engine = CheckpointEngine(str(tmp_path / "ckpts"))
        try:
            assert engine.save_to_memory(5, _state(5), block=True)
            step, restored = engine.load(_state(0))
        finally:
            engine.close()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))
        assert step == 5
        assert float(restored["params"]["w"][0, 0]) == 5.0
        stats = engine.last_restore_stats
        assert stats["source"] == "memory" and stats["verify_s"] == 0.0
        spans = _spans(tracer.events)
        restore = _one(spans, "ckpt.restore")
        assert restore["args"]["source"] == "memory"
        assert [e["name"] for e in _children(spans, restore)] == [
            "ckpt.restore_wait_staged", "ckpt.restore_locate",
            "ckpt.restore_copy", "ckpt.restore_read", "ckpt.restore_copy",
        ]
        self._check_stats(spans, restore, stats)

    def test_the_trainers_restore_holds_the_load_and_the_put(
        self, tracer, job_name, tmp_path
    ):
        ckpt_dir = str(tmp_path / "flash")
        trainer, batches = _tiny_trainer(
            checkpoint_dir=ckpt_dir, persist_every=2
        )
        try:
            trainer.fit(batches(), steps=2)
            assert trainer.checkpointer.wait_persisted(2, timeout=60.0)
        finally:
            trainer.close()
        again, _ = _tiny_trainer(checkpoint_dir=ckpt_dir)
        try:
            assert again.restore() == 2
        finally:
            again.close()
        spans = _spans(tracer.events)
        outer = [e for e in spans.values() if e["name"] == "trainer.restore"]
        assert [e["args"]["step"] for e in outer] == [-1, 2]
        assert [e["name"] for e in _children(spans, outer[0])] == [
            "ckpt.restore"]
        assert [e["name"] for e in _children(spans, outer[1])] == [
            "ckpt.restore", "trainer.restore_put"]
