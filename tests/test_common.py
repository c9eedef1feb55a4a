"""Unit tests for the common substrate: shm, shared objects, storage, rpc."""

import multiprocessing as mp
import os
import queue
import uuid

import numpy as np
import pytest

from dlrover_tpu.common import messages
from dlrover_tpu.common.comm import SharedDict, SharedLock, SharedQueue
from dlrover_tpu.common.rpc import RpcClient, RpcServer, find_free_port
from dlrover_tpu.common.shared_memory import SharedMemory
from dlrover_tpu.common.storage import PosixDiskStorage


def _shm_child(n):
    s = SharedMemory(n, create=True, size=256)
    s.buf[:4] = b"abcd"
    # die without cleanup


def _lock_holding_child(job):
    c = SharedLock("l2", job=job)
    assert c.acquire()
    # die holding the lock


class TestSharedMemory:
    def test_create_attach_persist(self):
        name = f"shm-{uuid.uuid4().hex[:8]}"
        shm = SharedMemory(name, create=True, size=1024)
        arr = np.frombuffer(shm.buf, dtype=np.float32)
        arr[:10] = np.arange(10, dtype=np.float32)
        shm.close()  # closing must NOT unlink

        assert SharedMemory.exists(name)
        shm2 = SharedMemory(name)
        arr2 = np.frombuffer(shm2.buf, dtype=np.float32)
        np.testing.assert_array_equal(arr2[:10], np.arange(10, dtype=np.float32))
        shm2.unlink()
        assert not SharedMemory.exists(name)

    def test_survives_child_death(self):
        name = f"shm-{uuid.uuid4().hex[:8]}"
        p = mp.get_context("spawn").Process(target=_shm_child, args=(name,))
        p.start()
        p.join()
        assert SharedMemory.exists(name)
        s = SharedMemory(name)
        assert bytes(s.buf[:4]) == b"abcd"
        s.unlink()


    def test_segment_above_the_file_size_limit_is_kept_as_parts(
        self, file_size_limit
    ):
        """The refusal on the driver's chip machine: a 6.3 GB snapshot
        segment under an RLIMIT_FSIZE below that died in ftruncate with
        EFBIG. Here: 1 MiB limit, 3 MiB + 5 byte segment."""
        name = f"shm-{uuid.uuid4().hex[:8]}"
        size = 3 * (1 << 20) + 5
        want = (np.arange(size) % 251).astype(np.uint8)
        with file_size_limit(1 << 20):
            shm = SharedMemory(name, create=True, size=size)
        try:
            files = sorted(
                f for f in os.listdir("/dev/shm") if f.startswith(name)
            )
            assert files == [name] + [f"{name}.part{i}" for i in (1, 2, 3)]
            assert all(
                os.path.getsize(f"/dev/shm/{f}") < (1 << 20) for f in files
            )
            assert shm.size == size
            np.frombuffer(shm.buf, dtype=np.uint8)[:] = want
            # Another process (the agent) attaches under no limit at all
            # and sees one contiguous buffer.
            other = SharedMemory(name)
            assert other.size == size
            np.testing.assert_array_equal(
                np.frombuffer(other.buf, dtype=np.uint8), want
            )
            other.close()
            # A smaller segment of the same name leaves no stale part.
            shm.close()
            with file_size_limit(1 << 20):
                shm = SharedMemory(name, create=True, size=(1 << 20) + 7)
            assert SharedMemory(name).size == (1 << 20) + 7
        finally:
            shm.unlink()
        assert not [f for f in os.listdir("/dev/shm") if f.startswith(name)]


class TestSharedObjects:
    def test_lock(self, job_name):
        lock = SharedLock("l1", create=True)
        client = SharedLock("l1")
        other = SharedLock("l1")  # distinct owner token, same process
        assert client.acquire()
        assert lock.locked()
        # Same owner: idempotent (rpc-retry safety); other owner: blocked.
        assert client.acquire(blocking=False)
        assert not other.acquire(blocking=False)
        assert not other.release()  # non-owner release refused
        assert client.release()
        assert not lock.locked()
        assert other.acquire(blocking=False)
        assert other.release()
        lock.close()

    def test_lock_dead_owner_force_release(self, job_name):
        lock = SharedLock("l2", create=True)
        p = mp.get_context("spawn").Process(
            target=_lock_holding_child, args=(job_name,)
        )
        p.start()
        p.join()
        # The dead owner must not wedge the lock: a live client acquires.
        survivor = SharedLock("l2")
        assert survivor.acquire(timeout=10)
        assert survivor.release()
        lock.close()

    def test_queue(self, job_name):
        q = SharedQueue("q1", create=True)
        client = SharedQueue("q1")
        client.put({"step": 7})
        assert q.qsize() == 1
        assert client.get(timeout=5) == {"step": 7}
        with pytest.raises(queue.Empty):
            client.get(block=False)
        q.close()

    def test_dict(self, job_name):
        d = SharedDict("d1", create=True)
        client = SharedDict("d1")
        client.set("a", 1)
        client.update({"b": [1, 2]})
        assert client.get("a") == 1
        assert client.copy() == {"a": 1, "b": [1, 2]}
        assert client.pop("a") == 1
        assert client.get("a") is None
        d.close()


class TestStorage:
    def test_roundtrip_and_atomic_rename(self, tmp_path):
        st = PosixDiskStorage()
        p = str(tmp_path / "x.bin")
        st.write_bytes(b"hello", p)
        assert st.read_bytes(p) == b"hello"
        st.safe_rename(p, str(tmp_path / "y.bin"))
        assert not st.exists(p)
        assert st.read(str(tmp_path / "y.bin"), "rb") == b"hello"
        st.safe_makedirs(str(tmp_path / "d" / "e"))
        assert st.listdir(str(tmp_path / "d")) == ["e"]
        st.safe_remove(str(tmp_path / "d"))
        assert not st.exists(str(tmp_path / "d"))


    def test_file_above_the_file_size_limit_is_kept_as_parts(
        self, tmp_path, file_size_limit
    ):
        st = PosixDiskStorage()
        p = str(tmp_path / "shard_0.bin")
        data = (np.arange(3 * (1 << 20) + 5) % 251).astype(np.uint8).tobytes()
        mv = memoryview(data)
        with file_size_limit(1 << 20):
            # buffers that end on, straddle and skip part boundaries
            st.write_chunks(
                [mv[:100], mv[100:(1 << 20) + 50], mv[(1 << 20) + 50:]], p
            )
        names = ["shard_0.bin"] + [f"shard_0.bin.part{i}" for i in (1, 2, 3)]
        assert sorted(os.listdir(tmp_path)) == names
        assert all(
            os.path.getsize(tmp_path / n) < (1 << 20) for n in names
        )
        # Readers need no limit and no record of the part size.
        assert st.read_bytes(p) == data
        lo = (1 << 20) - 5000
        assert st.read_range(p, lo, 10000) == data[lo:lo + 10000]
        with st.open_reader(p) as r:
            assert r.size() == len(data)
            into = bytearray(2 * (1 << 20))
            assert r.read_into(1000, into) == len(into)
            assert bytes(into) == data[1000:1000 + len(into)]
            assert r.read(len(data) - 10, 100) == data[-10:]
        # Out-of-order positional writes into a preallocated file.
        with file_size_limit(1 << 20):
            with st.open_writer(p, len(data)) as w:
                w.write_at(2 << 20, mv[2 << 20:])
                w.writev_at(0, [mv[:7], mv[7:2 << 20]])
        assert st.read_bytes(p) == data
        st.safe_rename(p, str(tmp_path / "moved.bin"))
        assert st.read_bytes(str(tmp_path / "moved.bin")) == data
        st.safe_rename(str(tmp_path / "moved.bin"), p)
        # Rewritten under no limit it is one file again, no stale part.
        st.write_bytes(data[:10], p)
        assert os.listdir(tmp_path) == ["shard_0.bin"]
        assert st.read_bytes(p) == data[:10]
        with file_size_limit(1 << 20):
            st.write_bytes(data, p)
        st.safe_remove(p)
        assert os.listdir(tmp_path) == []


class TestRpc:
    def test_request_response_and_error(self):
        def handler(req):
            if isinstance(req, messages.KVStoreGet):
                return messages.KVStoreSet(key=req.key, value=b"v")
            raise ValueError("unknown message")

        server = RpcServer(0, handler)
        server.start()
        client = RpcClient(f"127.0.0.1:{server.port}")
        resp = client.call(messages.KVStoreGet(key="k"))
        assert resp.value == b"v"
        with pytest.raises(RuntimeError):
            client.call(messages.JobExitRequest())
        client.close()
        server.stop()

    def test_find_free_port(self):
        assert find_free_port() > 0

    def test_retry_dedup(self):
        """A retried request id must be applied once and answered from cache."""
        counter = {"n": 0}

        def handler(req):
            counter["n"] += 1
            return counter["n"]

        server = RpcServer(0, handler)
        server.start()
        import socket as socket_mod

        from dlrover_tpu.common.rpc import _recv, _send

        s = socket_mod.create_connection(("127.0.0.1", server.port))
        envelope = ("fixed-req-id", messages.KVStoreAdd(key="k"))
        _send(s, envelope)
        ok1, v1 = _recv(s)
        _send(s, envelope)  # simulated retry after a lost response
        ok2, v2 = _recv(s)
        assert ok1 and ok2
        assert v1 == v2 == 1
        assert counter["n"] == 1
        s.close()
        server.stop()


class TestNativeCopyEngine:
    """The C++ copy engine must be byte-identical to the numpy pool."""

    def test_native_builds_and_copies(self):
        import numpy as np

        from dlrover_tpu.common import fastcopy

        lib = fastcopy._native()
        if lib is None:
            import pytest

            pytest.skip("no C++ toolchain in this environment")
        rng = np.random.default_rng(0)
        src1 = rng.integers(0, 255, 5 << 20, dtype=np.uint8)
        src2 = rng.integers(0, 255, 3 << 20, dtype=np.uint8)
        dst1 = np.zeros_like(src1)
        dst2 = np.zeros_like(src2)
        fastcopy.copy_many([(dst1, src1), (dst2, src2)])
        np.testing.assert_array_equal(dst1, src1)
        np.testing.assert_array_equal(dst2, src2)

    def test_fallback_forced(self, monkeypatch):
        import numpy as np

        from dlrover_tpu.common import fastcopy

        monkeypatch.setattr(fastcopy, "_NATIVE", None)
        monkeypatch.setattr(fastcopy, "_NATIVE_TRIED", True)
        src = np.arange(2 << 20, dtype=np.uint8)
        dst = np.zeros_like(src)
        fastcopy.copy_many([(dst, src)])
        np.testing.assert_array_equal(dst, src)

    def test_native_bandwidth_sane(self):
        """The native path must not be slower than a single-thread copy
        (soft perf floor, catches pathological binding overhead)."""
        import time

        import numpy as np

        from dlrover_tpu.common import fastcopy

        if fastcopy._native() is None:
            import pytest

            pytest.skip("no native engine")
        src = np.ones(256 << 20, dtype=np.uint8)
        dst = np.empty_like(src)
        dst[:] = 0  # pre-fault: page faults must not bill either timing
        # Warm the engine (lazy .so load + thread calibration) outside
        # the timed region, and take best-of-3 on both sides: this is a
        # pathological-overhead floor, not a bench, and the shared CI
        # host is noisy.
        fastcopy.copy_many([(dst[:1 << 20], src[:1 << 20])])
        native_s = single_s = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            fastcopy.copy_many([(dst, src)])
            native_s = min(native_s, time.perf_counter() - t0)
            t0 = time.perf_counter()
            dst[:] = src
            single_s = min(single_s, time.perf_counter() - t0)
        assert native_s < single_s * 2.0, (
            f"native {native_s:.3f}s vs single-thread {single_s:.3f}s"
        )
