"""Flash-checkpoint tests: shm staging, two-phase commit, crash flush,
dirty-write refusal, memory + storage restore.

Parity with the reference's test strategy (SURVEY.md §4.4): real shared
memory, real locks/queues/dicts, tmp dirs as storage.
"""

import os
import pickle

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
from dlrover_tpu.common import ckpt_persist
from dlrover_tpu.common.ckpt_meta import (
    ckpt_lock_name,
    ckpt_shm_name,
)
from dlrover_tpu.common.comm import SharedLock
from dlrover_tpu.common.constants import CheckpointConstant
from dlrover_tpu.common.shared_memory import SharedMemory
from dlrover_tpu.common.storage import PosixDiskStorage
from dlrover_tpu.train.checkpoint import CheckpointEngine
from dlrover_tpu.train.checkpoint.checkpointer import (
    FlashCheckpointer,
    StorageType,
)


def make_state(seed=0):
    w = jnp.arange(12, dtype=jnp.float32).reshape(3, 4) + seed
    opt = optax.adam(0.1)
    return {
        "params": {"w": w, "b": jnp.ones((4,)) * seed},
        "opt": opt.init(w),
        "step": seed,
    }


def assert_state_equal(a, b):
    import jax

    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y))


@pytest.fixture
def saver_env(job_name, tmp_path):
    """An in-process agent-side saver + cleanup of shm/singletons."""
    yield str(tmp_path / "ckpts")
    AsyncCheckpointSaver.stop()
    SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))


class TestStandaloneEngine:
    def test_roundtrip_via_storage(self, job_name, tmp_path):
        ckpt_dir = str(tmp_path / "ckpts")
        state = make_state(3)
        engine = CheckpointEngine(ckpt_dir)
        try:
            assert engine.save_to_storage(7, state)
            assert ckpt_persist.read_tracker(
                PosixDiskStorage(), ckpt_dir
            ) == 7
            step, restored = CheckpointEngine(ckpt_dir).load(make_state(0))
            assert step == 7
            assert_state_equal(restored, state)
        finally:
            engine.close()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))

    def test_roundtrip_under_a_file_size_limit(
        self, job_name, tmp_path, file_size_limit
    ):
        """A state larger than RLIMIT_FSIZE snapshots, persists and
        restores from memory and from storage (shm segment and shard
        file kept as parts, ``common/fsutil.py``)."""
        ckpt_dir = str(tmp_path / "ckpts")
        state = make_state(3)
        state["params"]["big"] = jnp.arange(
            3 << 18, dtype=jnp.float32
        ).reshape(3, -1)  # 3 MiB
        template = dict(make_state(0), params=dict(
            make_state(0)["params"], big=jnp.zeros_like(state["params"]["big"])
        ))
        engine = CheckpointEngine(ckpt_dir)
        try:
            with file_size_limit(1 << 20):
                assert engine.save_to_storage(7, state)
                step, restored = engine.load(template)
            assert step == 7
            assert engine.last_restore_stats["source"] == "memory"
            assert_state_equal(restored, state)
            step_dir = os.path.join(ckpt_dir, "checkpoint-7")
            assert "shard_0.bin.part3" in os.listdir(step_dir)
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))
            fresh = CheckpointEngine(ckpt_dir)
            step, restored = fresh.load(template)
            fresh.close()
            assert step == 7
            assert fresh.last_restore_stats["source"] == "storage"
            assert_state_equal(restored, state)
        finally:
            engine.close()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))

    def test_restore_phase_attribution(self, job_name, tmp_path):
        """Every load reports a read/assemble/device_put
        breakdown so slow restores are attributable (vs the reference's
        unquantified seconds-from-shm claim)."""
        ckpt_dir = str(tmp_path / "ckpts")
        state = make_state(3)
        engine = CheckpointEngine(ckpt_dir)
        try:
            assert engine.save_to_storage(7, state)
            loader = CheckpointEngine(ckpt_dir)
            step, _ = loader.load(make_state(0))
            assert step == 7
            stats = loader.last_restore_stats
            # saver restores from its own memory snapshot; a fresh
            # engine has no snapshot and must hit storage
            assert stats["source"] == "storage"
            assert stats["bytes"] > 0
            assert stats["read_s"] > 0.0
            assert stats["total_s"] >= (
                stats["read_s"] + stats["device_put_s"]
            )
            assert stats["assemble_s"] >= 0.0
            # and the memory path stamps its source too
            step, _ = engine.load(make_state(0))
            assert engine.last_restore_stats["source"] == "memory"
        finally:
            engine.close()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))

    def test_load_without_checkpoint(self, job_name, tmp_path):
        engine = CheckpointEngine(str(tmp_path / "none"))
        template = make_state(0)
        step, restored = engine.load(template)
        assert step == -1
        assert restored is template

    def test_two_phase_commit_files(self, job_name, tmp_path):
        ckpt_dir = str(tmp_path / "ckpts")
        engine = CheckpointEngine(ckpt_dir)
        try:
            engine.save_to_storage(1, make_state(1))
            d = ckpt_persist.step_dir(ckpt_dir, 1)
            names = sorted(os.listdir(d))
            assert "shard_0.bin" in names
            assert "shard_0.meta" in names
            assert "done_0" in names
            assert os.path.exists(
                os.path.join(ckpt_dir, CheckpointConstant.TRACKER_FILE)
            )
        finally:
            engine.close()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))

    def test_gc_keeps_latest(self, job_name, tmp_path):
        ckpt_dir = str(tmp_path / "ckpts")
        engine = CheckpointEngine(ckpt_dir, keep_latest=2)
        try:
            for s in (1, 2, 3, 4):
                engine.save_to_storage(s, make_state(s))
            steps = ckpt_persist.list_steps(PosixDiskStorage(), ckpt_dir)
            assert steps == [3, 4]
        finally:
            engine.close()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))


class TestAgentModeEngine:
    def _start_agent_side(self):
        AsyncCheckpointSaver.start_async_saving_ckpt()

    def _wait_saver(self, timeout=10.0):
        import time

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            saver = AsyncCheckpointSaver.get_ckpt_saver()
            if saver is not None:
                return saver
            time.sleep(0.05)
        raise TimeoutError("saver never registered")

    def test_memory_save_and_restore(self, saver_env):
        self._start_agent_side()
        state = make_state(5)
        engine = CheckpointEngine(saver_env)
        try:
            assert engine.agent_mode
            assert engine.save_to_memory(9, state)
            self._wait_saver()
            # A fresh engine (simulating a restarted trainer) restores the
            # memory snapshot without touching disk.
            engine2 = CheckpointEngine(saver_env)
            step, restored = engine2.load(make_state(0))
            assert step == 9
            assert_state_equal(restored, state)
        finally:
            engine.close()

    def test_async_disk_persist_and_commit(self, saver_env):
        self._start_agent_side()
        state = make_state(2)
        engine = CheckpointEngine(saver_env)
        try:
            assert engine.save_to_storage(4, state)
            assert engine.wait_persisted(4, timeout=90.0)
            shard = ckpt_persist.load_shard(
                PosixDiskStorage(), saver_env, 4, 0
            )
            assert shard is not None
        finally:
            engine.close()

    def test_crash_flush_persists_memory_snapshot(self, saver_env):
        self._start_agent_side()
        state = make_state(8)
        engine = CheckpointEngine(saver_env)
        try:
            # Memory-only save: nothing on disk yet.
            assert engine.save_to_memory(11, state)
            saver = self._wait_saver()
            assert ckpt_persist.read_tracker(
                PosixDiskStorage(), saver_env
            ) is None
            # The agent's crash flush persists the snapshot.
            saver.save_shm_to_storage(commit_timeout=30.0)
            assert ckpt_persist.read_tracker(
                PosixDiskStorage(), saver_env
            ) == 11
            step, restored = CheckpointEngine(saver_env).load(make_state(0))
            assert step == 11
            assert_state_equal(restored, state)
        finally:
            engine.close()

    def test_dirty_write_refusal(self, saver_env, job_name):
        self._start_agent_side()
        engine = CheckpointEngine(saver_env)
        try:
            assert engine.save_to_memory(1, make_state(1))
            self._wait_saver()
            # Another client (the saver persist thread, in real life) holds
            # the shard lock: the engine skips instead of tearing the buffer.
            other = SharedLock(ckpt_lock_name(0, 0), create=False,
                               job=job_name)
            assert other.acquire(timeout=5.0)
            try:
                assert not engine.save_to_memory(2, make_state(2))
            finally:
                other.release()
            assert engine.save_to_memory(2, make_state(2))
        finally:
            engine.close()

    def test_async_memory_save(self, saver_env):
        """Async staging: save returns immediately, snapshot lands after
        wait_staged, restore sees it."""
        self._start_agent_side()
        state = make_state(4)
        engine = CheckpointEngine(saver_env)
        try:
            assert engine.save_to_memory_async(3, state)
            assert engine.wait_staged(timeout=30.0)
            self._wait_saver()
            step, restored = CheckpointEngine(saver_env).load(make_state(0))
            assert step == 3
            assert_state_equal(restored, state)
        finally:
            engine.close()

    def test_async_ordering_with_sync_save(self, saver_env):
        """A sync save issued after an async one must not be overwritten by
        the older staging completing later."""
        self._start_agent_side()
        engine = CheckpointEngine(saver_env)
        try:
            engine.save_to_memory_async(1, make_state(1))
            assert engine.save_to_memory(2, make_state(2), block=True)
            assert engine._memory_meta().step == 2
        finally:
            engine.close()

    def test_saver_skips_step_moved_under_lock(self, saver_env):
        """A shard that advanced past the event's step is not persisted into
        the wrong step dir."""
        self._start_agent_side()
        engine = CheckpointEngine(saver_env)
        try:
            engine.save_to_memory(1, make_state(1))
            saver = self._wait_saver()
            meta = saver._local_metas()[0]
            engine.save_to_memory(2, make_state(2))
            stale = pickle.loads(pickle.dumps(meta))
            assert not saver._persist_one(0, stale)
        finally:
            engine.close()


class TestFlashCheckpointerAPI:
    def test_user_loop(self, saver_env, job_name):
        AsyncCheckpointSaver.start_async_saving_ckpt()
        ckpt = FlashCheckpointer(saver_env)
        try:
            state = make_state(1)
            step, state = ckpt.load_checkpoint(state)
            assert step == -1
            last_memory = -1
            for s in range(1, 6):
                state["step"] = s
                st = (
                    StorageType.DISK if s % 2 == 0 else StorageType.MEMORY
                )
                ok = ckpt.save_checkpoint(s, state, st)
                # DISK saves block for the lock and must never be dropped;
                # MEMORY saves may legitimately skip under saver contention
                # or while a previous async staging is in flight.
                if st == StorageType.DISK:
                    assert ok
                if ok:
                    last_memory = s
            # Join only: the result is False when the async snapshot of
            # step 3 was still in flight at step 4's DISK save, which
            # supersedes it (seen once in a loaded tier-1 run). What must
            # hold is the restored step below.
            ckpt.engine.wait_staged()
            assert ckpt.wait_persisted(4, timeout=90.0)
            # The newest staged snapshot wins on restore.
            step, restored = FlashCheckpointer(saver_env).load_checkpoint(
                make_state(0)
            )
            assert step == last_memory
        finally:
            ckpt.close()


class TestStepConsistencyVote:
    """Multi-process restore must agree on one step (kv-store vote —
    the reference allgathers on gloo, reference ``engine.py:64``)."""

    def _vote(self, master, tmp_path, monkeypatch, steps):
        import threading

        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.common.constants import NodeEnv

        monkeypatch.setenv(NodeEnv.MASTER_ADDR, master.addr)
        monkeypatch.setenv(NodeEnv.NUM_PROCESSES, str(len(steps)))
        MasterClient.reset()
        engines = []
        for rank in range(len(steps)):
            monkeypatch.setenv(NodeEnv.PROCESS_ID, str(rank))
            engines.append(CheckpointEngine(str(tmp_path / "ck")))
        results = [None] * len(steps)

        def vote(i):
            results[i] = engines[i]._consistent_memory_step(steps[i])

        threads = [
            threading.Thread(target=vote, args=(i,))
            for i in range(len(steps))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=90)
        for e in engines:
            e.close()
        MasterClient.reset()
        return results

    @pytest.fixture
    def master(self):
        from dlrover_tpu.master.master import JobMaster

        master = JobMaster(port=0, node_num=2, job_name="vote-test")
        master.prepare()
        yield master
        master.stop()

    def test_agreement_restores_memory(self, master, tmp_path, monkeypatch,
                                       job_name):
        assert self._vote(master, tmp_path, monkeypatch, [7, 7]) == [
            True, True,
        ]

    def test_disagreement_falls_back_to_storage(self, master, tmp_path,
                                                monkeypatch, job_name):
        """A torn flush (nodes at different steps) must NOT memory-restore
        anywhere — every rank falls back to committed storage."""
        assert self._vote(master, tmp_path, monkeypatch, [7, 9]) == [
            False, False,
        ]

    def test_missing_snapshot_votes_minus_one(self, master, tmp_path,
                                              monkeypatch, job_name):
        assert self._vote(master, tmp_path, monkeypatch, [7, -1]) == [
            False, False,
        ]


class TestQuantizedStateCheckpoint:
    """The 8-bit optimizer's int8/_QTensor pytree must round-trip
    through the flash engines byte-exactly (namedtuple structure, int8
    moments of the parameter's shape, fp32 scales ``[..., blocks,
    rows]``), and hold no bit a step leaves unwritten: the benchmark's
    elastic cell fingerprints every one across a kill."""

    @staticmethod
    def _two_updates():
        import jax

        from dlrover_tpu.optim.low_bit import adam8bit

        rng = np.random.default_rng(3)
        draw = lambda shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
        params = {
            "stack": draw((4, 8, 320)),   # scanned; a tail block of 64
            "w": draw((1100, 72)),        # rows past the kernel's tile
            "down": draw((2, 384, 200)),  # lies transposed on the chip
            "b": draw((300,)),
        }
        opt = adam8bit(1e-2)
        opt_state = opt.init(params)
        for _ in range(2):
            grads = jax.tree_util.tree_map(lambda p: draw(p.shape), params)
            params, opt_state = opt.update_and_apply(
                grads, opt_state, params
            )
        return {"params": params, "opt": opt_state, "step": 2}

    @pytest.mark.parametrize("storage", ["MEMORY", "DISK"])
    def test_adam8bit_state_round_trips(self, tmp_path, job_name, storage):
        import jax

        from benchmark.worker import fingerprint

        state = self._two_updates()
        ckpt = FlashCheckpointer(str(tmp_path / "ckpts"))
        try:
            assert ckpt.save_checkpoint(2, state, getattr(StorageType, storage))
            zeros = jax.tree_util.tree_map(jnp.zeros_like, state)
            if storage == "DISK":   # no warm snapshot: read what was written
                ckpt.engine.wait_staged(60.0)
                SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))
            step, restored = ckpt.load_checkpoint(zeros)
            assert step == 2
            source = ckpt.engine.last_restore_stats["source"]
            assert source == ("memory" if storage == "MEMORY" else "storage")
            for a, b in zip(
                jax.tree_util.tree_leaves(state),
                jax.tree_util.tree_leaves(restored),
            ):
                if hasattr(a, "dtype"):
                    assert a.dtype == b.dtype
                    assert a.shape == b.shape
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)
                )
            np.testing.assert_array_equal(
                np.asarray(fingerprint(state)),
                np.asarray(fingerprint(restored)),
            )
        finally:
            ckpt.close()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))

    def test_two_runs_leave_the_same_bits(self):
        """No scale and no ``q`` element is left to whatever the buffer
        held: the same two updates twice give equal fingerprints."""
        from benchmark.worker import fingerprint

        np.testing.assert_array_equal(
            np.asarray(fingerprint(self._two_updates())),
            np.asarray(fingerprint(self._two_updates())),
        )
