"""Striped parallel checkpoint I/O: stripe planning, the pipelined
persist, positional readers/writers, stripe-level corruption reporting,
the engine's fallback on a striped-corrupt step, and old-format
compatibility. Plus the bench-delta comparison tool.
"""

import dataclasses
import os
import pickle

import numpy as np
import pytest

from dlrover_tpu.common import checksum, ckpt_persist
from dlrover_tpu.common.ckpt_meta import (
    ShardMeta,
    TensorMeta,
    ckpt_shm_name,
)
from dlrover_tpu.common.shared_memory import SharedMemory
from dlrover_tpu.common.storage import (
    CheckpointStorage,
    PosixDiskStorage,
    RangeReader,
    StripeWriter,
)


def make_state(seed=0):
    import jax.numpy as jnp
    import optax

    w = jnp.arange(12, dtype=jnp.float32).reshape(3, 4) + seed
    opt = optax.adam(0.1)
    return {
        "params": {"w": w, "b": jnp.ones((4,)) * seed},
        "opt": opt.init(w),
        "step": seed,
    }


def _shard(total, block_sizes):
    """A synthetic shard: deterministic payload + metas over the blocks."""
    buf = np.frombuffer(
        np.random.default_rng(7).bytes(total), dtype=np.uint8
    )
    tensors, off = [], 0
    for i, n in enumerate(block_sizes):
        tensors.append(TensorMeta(
            path=f"leaf_{i}", offset=off, nbytes=n, dtype="uint8",
            shape=(n,),
        ))
        off += n
    assert off == total
    meta = ShardMeta(step=1, used_bytes=total, tensors=tensors)
    return meta, buf


class TestStripePlanning:
    def test_plan_covers_every_byte_in_order(self):
        chunks = [memoryview(bytes([i]) * n)
                  for i, n in enumerate((10, 3, 25, 1, 11))]
        plan = ckpt_persist._plan_stripes(chunks, 16)
        # Offsets are contiguous and stripes are full except the last.
        expect_off = 0
        for k, (off, views) in enumerate(plan):
            assert off == expect_off
            n = sum(v.nbytes for v in views)
            if k < len(plan) - 1:
                assert n == 16
            expect_off += n
        assert expect_off == 50
        flat = b"".join(
            bytes(v) for _, views in plan for v in views
        )
        assert flat == b"".join(bytes(c) for c in chunks)

    def test_plan_aliases_input_memory(self):
        # Stripes must be views over the input chunks, never copies.
        src = bytearray(100)
        plan = ckpt_persist._plan_stripes([memoryview(src)], 32)
        src[50] = 0xAB
        assert bytes(plan[1][1][0])[18] == 0xAB

    def test_stripe_env_config(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_CKPT_STRIPE_MB", "0")
        assert ckpt_persist.stripe_bytes_config() == 0
        monkeypatch.setenv("DLROVER_TPU_CKPT_STRIPE_MB", "64")
        assert ckpt_persist.stripe_bytes_config() == 64 << 20
        # Sub-MB configs clamp up; garbage falls back to the default.
        monkeypatch.setenv("DLROVER_TPU_CKPT_STRIPE_MB", "0.001")
        assert ckpt_persist.stripe_bytes_config() == 1 << 20
        monkeypatch.setenv("DLROVER_TPU_CKPT_STRIPE_MB", "banana")
        assert ckpt_persist.stripe_bytes_config() == (
            ckpt_persist.DEFAULT_STRIPE_MB << 20
        )


class TestStripedPersist:
    def _persist(self, storage, ckpt_dir, meta, buf, stripe_mb,
                 monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_CKPT_STRIPE_MB", str(stripe_mb))
        return ckpt_persist.persist_shard(
            storage, ckpt_dir, meta, memoryview(buf)
        )

    def test_striped_and_serial_bins_byte_identical(
        self, tmp_path, monkeypatch
    ):
        total = 3 * (1 << 20) + 17  # spans stripes, ragged tail
        meta, buf = _shard(total, [1 << 20, (1 << 20) + 9, 1 << 20, 8])
        st = PosixDiskStorage()
        stats_a = self._persist(
            st, str(tmp_path / "a"), meta, buf, 0, monkeypatch
        )
        stats_b = self._persist(
            st, str(tmp_path / "b"), meta, buf, 1, monkeypatch
        )
        bin_a = open(
            ckpt_persist.shard_bin_path(str(tmp_path / "a"), 1, 0), "rb"
        ).read()
        bin_b = open(
            ckpt_persist.shard_bin_path(str(tmp_path / "b"), 1, 0), "rb"
        ).read()
        assert bin_a == bin_b and len(bin_a) == total
        assert stats_a["striped"] == 0.0 and stats_b["striped"] == 1.0
        # Meta formats diverge as designed: per-block CRCs vs stripes.
        meta_a = pickle.loads(open(
            ckpt_persist.shard_bin_path(str(tmp_path / "a"), 1, 0)[:-4]
            + ".meta", "rb"
        ).read())
        meta_b = pickle.loads(open(
            ckpt_persist.shard_bin_path(str(tmp_path / "b"), 1, 0)[:-4]
            + ".meta", "rb"
        ).read())
        assert meta_a.stripes is None
        assert all(isinstance(t.crc, int) for t in meta_a.tensors)
        assert len(meta_b.stripes) == 4  # ceil((3M+17)/1M)
        assert all(t.crc is None for t in meta_b.tensors)
        assert meta_b.stripe_bytes == 1 << 20

    @pytest.mark.parametrize("stripe_mb", [0, 1], ids=["serial", "striped"])
    def test_both_read_paths_give_back_the_source(
        self, tmp_path, monkeypatch, stripe_mb
    ):
        """What restore reads is what was persisted, block for block, on
        both of the engine's read paths: one shared reader after stripe
        verification, and open-per-block ``read_block`` with its
        per-block checksum."""
        total = 2 * (1 << 20) + 5
        meta, buf = _shard(total, [1 << 20, (1 << 20) - 3, 8])
        st, d = PosixDiskStorage(), str(tmp_path / "ckpts")
        self._persist(st, d, meta, buf, stripe_mb, monkeypatch)
        smeta = ckpt_persist.load_step_metas(st, d, 1)[0]
        reader = ckpt_persist.open_shard_reader(st, d, 1, 0)
        assert reader is not None
        try:
            ckpt_persist.verify_stripes(reader, smeta, 1, 0)
            for t in smeta.tensors:
                want = bytes(buf[t.offset:t.offset + t.nbytes])
                dst = np.empty(t.nbytes, dtype=np.uint8)
                assert reader.read_into(t.offset, memoryview(dst)) == t.nbytes
                assert dst.tobytes() == want
                assert ckpt_persist.read_block(
                    st, d, 1, 0, t, getattr(smeta, "crc_algo", "")
                ) == want
        finally:
            reader.close()

    def test_verify_step_ok_both_formats(self, tmp_path, monkeypatch):
        meta, buf = _shard(1 << 20, [1 << 20])
        st = PosixDiskStorage()
        for name, stripe_mb in (("a", 0), ("b", 1)):
            d = str(tmp_path / name)
            self._persist(st, d, meta, buf, stripe_mb, monkeypatch)
            st.write("1", os.path.join(d, "latest_checkpointed_iteration.txt"))
            ok, reason = ckpt_persist.verify_step(st, d, 1)
            assert ok, reason

    def test_flipped_byte_names_the_stripe(self, tmp_path, monkeypatch):
        total = 4 << 20
        meta, buf = _shard(total, [total])
        st = PosixDiskStorage()
        d = str(tmp_path / "c")
        self._persist(st, d, meta, buf, 1, monkeypatch)
        bin_path = ckpt_persist.shard_bin_path(d, 1, 0)
        raw = bytearray(open(bin_path, "rb").read())
        flip_at = (2 << 20) + 12345  # inside stripe 2 of 4
        raw[flip_at] ^= 0x01
        open(bin_path, "wb").write(bytes(raw))
        smeta = pickle.loads(
            open(bin_path[:-4] + ".meta", "rb").read()
        )
        reader = ckpt_persist.open_shard_reader(st, d, 1, 0)
        with pytest.raises(ckpt_persist.StepCorruptionError) as ei:
            ckpt_persist.verify_stripes(reader, smeta, 1, 0)
        reader.close()
        # Corruption localizes: the message names stripe 2, its byte
        # range and the algorithm — not just "shard bad".
        assert "stripe 2/4" in str(ei.value)
        assert f"offset {2 << 20}" in str(ei.value)
        ok, reason = ckpt_persist.verify_step(st, d, 1)
        assert not ok and "stripe 2/4" in reason

    def test_truncated_bin_reports_truncation(self, tmp_path, monkeypatch):
        total = 2 << 20
        meta, buf = _shard(total, [total])
        st = PosixDiskStorage()
        d = str(tmp_path / "t")
        self._persist(st, d, meta, buf, 1, monkeypatch)
        bin_path = ckpt_persist.shard_bin_path(d, 1, 0)
        raw = open(bin_path, "rb").read()
        open(bin_path, "wb").write(raw[:total - 1000])
        smeta = pickle.loads(open(bin_path[:-4] + ".meta", "rb").read())
        reader = ckpt_persist.open_shard_reader(st, d, 1, 0)
        with pytest.raises(ckpt_persist.StepCorruptionError) as ei:
            ckpt_persist.verify_stripes(reader, smeta, 1, 0)
        reader.close()
        assert "truncated" in str(ei.value)

    def test_persist_stats_reported(self, tmp_path, monkeypatch):
        meta, buf = _shard(1 << 20, [1 << 20])
        stats = self._persist(
            PosixDiskStorage(), str(tmp_path / "s"), meta, buf, 1,
            monkeypatch,
        )
        assert stats["bytes"] == float(1 << 20)
        assert stats["persist_s"] > 0 and stats["persist_mbps"] > 0
        assert stats["checksum_s"] >= 0


class TestEngineStripedRestore:
    def test_corrupt_striped_step_falls_back_to_older(
        self, job_name, tmp_path
    ):
        from dlrover_tpu.train.checkpoint import CheckpointEngine

        ckpt_dir = str(tmp_path / "ckpts")
        engine = CheckpointEngine(ckpt_dir, keep_latest=0)
        try:
            assert engine.save_to_storage(1, make_state(1))
            assert engine.save_to_storage(2, make_state(2))
        finally:
            engine.close()
        SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))
        # Flip one byte of step 2's striped bin: restore must detect it
        # via the stripe CRCs, quarantine step 2 and recover step 1.
        bin_path = ckpt_persist.shard_bin_path(ckpt_dir, 2, 0)
        raw = bytearray(open(bin_path, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(bin_path, "wb").write(bytes(raw))
        loader = CheckpointEngine(ckpt_dir, keep_latest=0)
        try:
            step, restored = loader.load(make_state(0))
            assert step == 1
            np.testing.assert_array_equal(
                np.asarray(restored["params"]["w"]),
                np.asarray(make_state(1)["params"]["w"]),
            )
        finally:
            loader.close()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))
        st = PosixDiskStorage()
        assert ckpt_persist.is_quarantined(st, ckpt_dir, 2)
        assert "stripe" in ckpt_persist.quarantine_reason(st, ckpt_dir, 2)

    def test_pre_stripe_checkpoint_restores_under_new_reader(
        self, job_name, tmp_path, monkeypatch
    ):
        from dlrover_tpu.train.checkpoint import CheckpointEngine

        ckpt_dir = str(tmp_path / "ckpts")
        # Write in the legacy format (per-block CRCs, no stripes) —
        # byte-for-byte what a pre-upgrade job left on disk.
        monkeypatch.setenv("DLROVER_TPU_CKPT_STRIPE_MB", "0")
        engine = CheckpointEngine(ckpt_dir, keep_latest=0)
        try:
            assert engine.save_to_storage(3, make_state(3))
        finally:
            engine.close()
        SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))
        smeta = pickle.loads(open(os.path.join(
            ckpt_persist.step_dir(ckpt_dir, 3), "shard_0.meta"
        ), "rb").read())
        assert smeta.stripes is None  # genuinely old-format on disk
        monkeypatch.delenv("DLROVER_TPU_CKPT_STRIPE_MB")
        loader = CheckpointEngine(ckpt_dir, keep_latest=0)
        try:
            step, restored = loader.load(make_state(0))
            assert step == 3
            np.testing.assert_array_equal(
                np.asarray(restored["params"]["w"]),
                np.asarray(make_state(3)["params"]["w"]),
            )
            stats = loader.last_restore_stats
            assert stats["source"] == "storage"
        finally:
            loader.close()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))


class TestStorageCapabilities:
    def test_posix_writer_out_of_order_atomic_commit(self, tmp_path):
        st = PosixDiskStorage()
        path = str(tmp_path / "f.bin")
        w = st.open_writer(path, size=10)
        w.write_at(5, b"world")
        # Nothing published before commit — only the staging .tmp.
        assert not os.path.exists(path)
        w.write_at(0, b"hello")
        w.commit()
        assert open(path, "rb").read() == b"helloworld"
        assert not os.path.exists(path + ".tmp")

    def test_posix_writer_abort_leaves_no_trace(self, tmp_path):
        st = PosixDiskStorage()
        path = str(tmp_path / "g.bin")
        try:
            with st.open_writer(path, size=4) as w:
                w.write_at(0, b"oops")
                raise RuntimeError("mid-persist crash")
        except RuntimeError:
            pass
        assert not os.path.exists(path)
        assert not os.path.exists(path + ".tmp")

    def test_posix_writer_scatter_gather(self, tmp_path):
        st = PosixDiskStorage()
        path = str(tmp_path / "h.bin")
        views = [memoryview(bytes([i]) * 3) for i in range(5)]
        with st.open_writer(path, size=15) as w:
            w.writev_at(0, views)
        assert open(path, "rb").read() == b"".join(
            bytes(v) for v in views
        )

    def test_posix_reader_pread_and_readinto(self, tmp_path):
        st = PosixDiskStorage()
        path = str(tmp_path / "r.bin")
        payload = bytes(range(256)) * 8
        st.write_bytes(payload, path)
        with st.open_reader(path) as r:
            assert r.size() == len(payload)
            assert r.read(100, 50) == payload[100:150]
            dst = np.zeros(64, dtype=np.uint8)
            assert r.read_into(512, memoryview(dst)) == 64
            assert bytes(dst) == payload[512:576]
        assert st.open_reader(str(tmp_path / "missing")) is None

    def test_base_writer_and_reader_fallbacks(self, tmp_path):
        # A minimal backend with no positional I/O of its own: the base
        # StripeWriter/RangeReader must make striping work anyway.
        st = PosixDiskStorage()
        path = str(tmp_path / "base.bin")
        w = StripeWriter(st, path, size=8)
        w.write_at(4, b"BBBB")
        w.write_at(0, b"AAAA")
        w.commit()
        assert open(path, "rb").read() == b"AAAABBBB"
        r = RangeReader(st, path)
        assert r.read(2, 4) == b"AABB"
        dst = bytearray(4)
        assert r.read_into(4, memoryview(dst)) == 4
        assert bytes(dst) == b"BBBB"

    def test_base_write_chunks_streams(self, tmp_path):
        writes = []

        class Recorder(PosixDiskStorage):
            def open_writer(self, path, size=None):
                w = super().open_writer(path, size)
                orig = w.writev_at

                def spy(offset, views):
                    writes.append(sum(
                        memoryview(v).nbytes for v in views
                    ))
                    orig(offset, views)

                w.writev_at = spy
                return w

        path = str(tmp_path / "chunks.bin")
        chunks = [bytes([i % 251]) * (1 << 20) for i in range(9)]
        Recorder().write_chunks(chunks, path)
        assert open(path, "rb").read() == b"".join(chunks)
        # Streamed in >=4MB scatter-gather batches, never one big join.
        assert len(writes) > 1
        assert max(writes) <= 5 << 20

    def test_posix_read_missing_returns_none(self, tmp_path):
        st = PosixDiskStorage()
        missing = str(tmp_path / "nope")
        assert st.read(missing) is None
        assert st.read_bytes(missing) is None
        assert st.read_range(missing, 0, 10) is None
