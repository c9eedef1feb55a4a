"""POSIX shared memory that survives process death.

Capability parity with the reference's ``common/multi_process.py:SharedMemory``
(a stdlib subclass that calls ``_posixshmem`` directly so the resource tracker
never auto-unlinks checkpoint buffers when a worker dies). Here we get the
same semantics more simply: a file under ``/dev/shm`` mapped with ``mmap``.
The segment lives until `unlink()` (or host reboot), exactly what a
flash-checkpoint buffer needs — the agent re-attaches to a dead trainer's
buffer and persists it.

A segment larger than the process's file-size limit is kept as part files
(``common/fsutil.py``) mapped back to back, so `buf` is one contiguous
view either way.
"""

import ctypes
import mmap
import os
from typing import List, Optional

from dlrover_tpu.common import env_utils, fsutil

SHM_DIR = env_utils.SHM_DIR.get()


def _path(name: str) -> str:
    safe = name.replace("/", "_")
    return os.path.join(SHM_DIR, safe)


_MAP_FIXED = 0x10  # Linux; the mmap module does not export it


def _map_parts(fds: List[int], sizes: List[int]) -> mmap.mmap:
    """One contiguous shared mapping of the files `fds` (every size but
    the last a multiple of the page size)."""
    if len(fds) == 1:
        return mmap.mmap(fds[0], sizes[0])
    # Reserve the address range with an anonymous map, then map each file
    # over its slice of it. The mmap object still owns the whole range:
    # its close() unmaps the files, and refuses while views are exported.
    libc = ctypes.CDLL(None, use_errno=True)
    libc.mmap.restype = ctypes.c_void_p
    libc.mmap.argtypes = [
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_long,
    ]
    region = mmap.mmap(-1, sum(sizes), flags=mmap.MAP_PRIVATE)
    anchor = ctypes.c_char.from_buffer(region)
    base = ctypes.addressof(anchor)
    del anchor  # release the export, or region.close() could never succeed
    offset = 0
    for fd, size in zip(fds, sizes):
        got = libc.mmap(
            base + offset, size, mmap.PROT_READ | mmap.PROT_WRITE,
            mmap.MAP_SHARED | _MAP_FIXED, fd, 0,
        )
        if got != base + offset:
            errno = ctypes.get_errno()
            region.close()
            raise OSError(errno, f"mmap of a shm part: {os.strerror(errno)}")
        offset += size
    return region


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
        fds: List[int] = []
        try:
            if create:
                if size <= 0:
                    raise ValueError("size must be > 0 when creating")
                part = fsutil.max_part_bytes()
                sizes = [min(part, size - o) for o in range(0, size, part)]
                # A larger earlier segment of this name may have had more.
                fsutil.remove_parts(self._file_path, len(sizes))
                for i, n in enumerate(sizes):
                    fd = os.open(
                        fsutil.part_path(self._file_path, i),
                        os.O_CREAT | os.O_RDWR, 0o600,
                    )
                    fds.append(fd)
                    if os.fstat(fd).st_size != n:
                        os.ftruncate(fd, n)
                    # Reserve the pages now: a tmpfs too small for the
                    # segment then fails here with ENOSPC, not with SIGBUS
                    # in the middle of a snapshot copy.
                    os.posix_fallocate(fd, 0, n)
            else:
                fds.append(os.open(self._file_path, os.O_RDWR))
                for path in fsutil.existing_parts(self._file_path)[1:]:
                    fds.append(os.open(path, os.O_RDWR))
                sizes = [os.fstat(fd).st_size for fd in fds]
                if sizes[0] == 0:
                    raise ValueError(f"shared memory {name} is empty")
            self._mmap = _map_parts(fds, sizes)
        except OSError:
            if create:  # leave no half-made segment holding tmpfs pages
                fsutil.remove_parts(self._file_path)
            raise
        finally:
            for fd in fds:
                os.close(fd)
        self._size = sum(sizes)
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
        fsutil.remove_parts(self._file_path)

    @staticmethod
    def exists(name: str) -> bool:
        return os.path.exists(_path(name))

    @staticmethod
    def remove(name: str):
        fsutil.remove_parts(_path(name))

    def __del__(self):  # close the map, never unlink implicitly
        try:
            self.close()
        except Exception:  # dtlint: disable=DT001 -- __del__ can run during interpreter teardown and must never raise
            pass
