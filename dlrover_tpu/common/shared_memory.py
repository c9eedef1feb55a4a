"""POSIX shared memory that survives process death.

Capability parity with the reference's ``common/multi_process.py:SharedMemory``
(a stdlib subclass that calls ``_posixshmem`` directly so the resource tracker
never auto-unlinks checkpoint buffers when a worker dies). Here we get the
same semantics more simply: a file under ``/dev/shm`` mapped with ``mmap``.
The segment lives until `unlink()` (or host reboot), exactly what a
flash-checkpoint buffer needs — the agent re-attaches to a dead trainer's
buffer and persists it.
"""

import mmap
import os
from typing import Optional

from dlrover_tpu.common import env_utils

SHM_DIR = env_utils.SHM_DIR.get()


def _path(name: str) -> str:
    safe = name.replace("/", "_")
    return os.path.join(SHM_DIR, safe)


class SharedMemory:
    """A named, persistent shared-memory segment.

    Unlike ``multiprocessing.shared_memory.SharedMemory`` (py3.12), the
    segment is never tracked by the resource tracker, so it outlives the
    creating process until explicitly unlinked.
    """

    def __init__(self, name: str, create: bool = False, size: int = 0):
        self.name = name
        self._file_path = _path(name)
        self._mmap: Optional[mmap.mmap] = None
        self._buf: Optional[memoryview] = None
        if create:
            if size <= 0:
                raise ValueError("size must be > 0 when creating")
            flags = os.O_CREAT | os.O_RDWR
            fd = os.open(self._file_path, flags, 0o600)
            try:
                cur = os.fstat(fd).st_size
                if cur != size:
                    os.ftruncate(fd, size)
                # Reserve the pages now: a tmpfs too small for the
                # segment then fails here with ENOSPC, not with SIGBUS
                # in the middle of a snapshot copy.
                os.posix_fallocate(fd, 0, size)
                self._mmap = mmap.mmap(fd, size)
            finally:
                os.close(fd)
            self._size = size
        else:
            fd = os.open(self._file_path, os.O_RDWR)
            try:
                self._size = os.fstat(fd).st_size
                if self._size == 0:
                    raise ValueError(f"shared memory {name} is empty")
                self._mmap = mmap.mmap(fd, self._size)
            finally:
                os.close(fd)
        self._buf = memoryview(self._mmap)

    @property
    def size(self) -> int:
        return self._size

    @property
    def buf(self) -> memoryview:
        assert self._buf is not None, "shared memory is closed"
        return self._buf

    def flush(self):
        if self._mmap is not None:
            self._mmap.flush()

    def close(self):
        # Best-effort detach: numpy views created over `buf` keep the buffer
        # exported; in that case the mapping stays alive until those arrays
        # are garbage-collected, which is the behavior we want (a saver
        # thread may still be persisting from a view).
        if self._buf is not None:
            try:
                self._buf.release()
                self._buf = None
            except BufferError:
                return
        if self._mmap is not None:
            try:
                self._mmap.close()
                self._mmap = None
            except BufferError:
                pass

    def unlink(self):
        self.close()
        try:
            os.unlink(self._file_path)
        except FileNotFoundError:
            pass

    @staticmethod
    def exists(name: str) -> bool:
        return os.path.exists(_path(name))

    @staticmethod
    def remove(name: str):
        try:
            os.unlink(_path(name))
        except FileNotFoundError:
            pass

    def __del__(self):  # close the map, never unlink implicitly
        try:
            self.close()
        except Exception:  # dtlint: disable=DT001 -- __del__ can run during interpreter teardown and must never raise
            pass
