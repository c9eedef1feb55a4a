"""Checkpoint storage abstraction (parity: reference ``common/storage.py``).

``CheckpointStorage`` is the ABC the async saver persists through;
``PosixDiskStorage`` is the default (local disk / NFS / GCS-fuse mounts).
``safe_rename`` + ``commit`` implement the atomic two-phase publish used by
flash checkpoint.

The striped checkpoint I/O pipeline (``common/ckpt_persist.py``) talks to
storage through two capability handles:

- :meth:`CheckpointStorage.open_writer` — positional writes into a
  staging location, committed atomically. ``PosixDiskStorage`` backs it
  with a preallocated ``.tmp`` file and ``os.pwrite``/``os.pwritev``
  (single fsync, then ``os.replace``); the base class buffers in memory
  and commits through :meth:`write_bytes`, so exotic backends and the
  chaos wrapper keep working unmodified.
- :meth:`CheckpointStorage.open_reader` — positional reads from one open
  handle. ``PosixDiskStorage`` keeps one file descriptor and serves
  ``os.pread``/``readinto`` directly into caller-owned views (pread is
  offset-addressed, so one reader is safe to share across the restore
  thread pool); the base class falls back to :meth:`read_range`.
"""

import os
import shutil
import sys
import threading
from abc import ABC, abstractmethod
from typing import Dict, List, Optional

from dlrover_tpu.common import fsutil

# os.pwritev takes at most IOV_MAX buffers per call; chunk conservatively.
_IOV_MAX = min(getattr(os, "IOV_MAX", 1024), 1024)


def _as_u8(data) -> memoryview:
    """A flat byte-typed memoryview over any contiguous buffer."""
    mv = data if isinstance(data, memoryview) else memoryview(data)
    if mv.ndim != 1 or mv.itemsize != 1:
        mv = mv.cast("B")
    return mv


class StripeWriter:
    """Positional write handle: ``write_at`` anywhere, then ``commit``
    publishes the file atomically (or ``abort`` leaves no trace).

    This base implementation buffers in memory and commits through the
    storage's ``write_bytes`` — correct for any backend (and exactly what
    the chaos wrapper needs: the whole file passes through one faultable
    write). Backends with positional I/O override ``open_writer`` to
    return a streaming handle instead.
    """

    def __init__(self, storage: "CheckpointStorage", path: str,
                 size: Optional[int] = None):
        self._storage = storage
        self._path = path
        self._buf = bytearray(size or 0)

    def write_at(self, offset: int, data) -> None:
        mv = _as_u8(data)
        end = offset + mv.nbytes
        if len(self._buf) < end:
            self._buf.extend(bytes(end - len(self._buf)))
        self._buf[offset:end] = mv

    def writev_at(self, offset: int, views: List[memoryview]) -> None:
        """Scatter-gather write of consecutive views starting at `offset`."""
        for v in views:
            self.write_at(offset, v)
            offset += _as_u8(v).nbytes

    def commit(self) -> None:
        self._storage.write_bytes(self._buf, self._path)

    def abort(self) -> None:
        self._buf = bytearray()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self.commit()
        else:
            self.abort()
        return False


class _PosixStripeWriter(StripeWriter):
    """pwrite/pwritev into a preallocated ``.tmp``, one fsync, atomic
    rename — the stripe pipeline's write side. Preallocation means
    positional writes never extend the file, so out-of-order stripes
    don't create sparse-then-filled metadata churn.

    Under a file-size limit the bytes go to part files of at most that
    size (``common/fsutil.py``); without one there is a single part."""

    def __init__(self, path: str, size: Optional[int] = None):
        self._path = path
        self._part = fsutil.max_part_bytes()
        self._fds: Dict[int, int] = {}
        try:
            for i, off in enumerate(range(0, size or 1, self._part)):
                fd = self._fd_of(i)
                if size:
                    os.ftruncate(fd, min(self._part, size - off))
        except OSError:
            self.abort()
            raise

    def _fd_of(self, index: int) -> int:
        if index not in self._fds:
            self._fds[index] = os.open(
                fsutil.part_path(self._path, index) + ".tmp",
                os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644,
            )
        return self._fds[index]

    def write_at(self, offset: int, data) -> None:
        self.writev_at(offset, [data])

    def writev_at(self, offset: int, views: List[memoryview]) -> None:
        iov = [mv for mv in map(_as_u8, views) if mv.nbytes]
        while iov:
            index, at = divmod(offset, self._part)
            # As many whole buffers as the call and this part will take,
            # or the head of one that crosses into the next part.
            room = self._part - at
            batch: List[memoryview] = []
            for mv in iov[:_IOV_MAX]:
                if mv.nbytes > room:
                    break
                batch.append(mv)
                room -= mv.nbytes
            if not batch:
                batch = [iov[0][:room]]
            n = os.pwritev(self._fd_of(index), batch, at)
            offset += n
            # Drop fully-written buffers; trim a partially-written head.
            while n:
                if n >= iov[0].nbytes:
                    n -= iov.pop(0).nbytes
                else:
                    iov[0] = iov[0][n:]
                    n = 0

    def commit(self) -> None:
        for fd in self._fds.values():
            os.fsync(fd)
            os.close(fd)
        # Part 0 names the file, so it lands last; parts a larger earlier
        # file of this name had beyond ours go.
        indices = sorted(self._fds, reverse=True)
        self._fds = {}
        for i in indices:
            tmp = fsutil.part_path(self._path, i) + ".tmp"
            os.replace(tmp, fsutil.part_path(self._path, i))
        fsutil.remove_parts(self._path, indices[0] + 1)

    def abort(self) -> None:
        for i, fd in self._fds.items():
            os.close(fd)
            try:
                os.remove(fsutil.part_path(self._path, i) + ".tmp")
            except OSError:
                pass
        self._fds = {}


class RangeReader:
    """Positional read handle over one stored file.

    ``read`` returns bytes (possibly short at EOF); ``read_into`` fills a
    caller-owned writable view and returns the byte count — the restore
    path points it straight at the preallocated destination arrays, so
    block bytes are copied exactly once. The base implementation goes
    through ``read_range`` per call; ``PosixDiskStorage`` overrides with
    a shared-fd pread."""

    def __init__(self, storage: "CheckpointStorage", path: str):
        self._storage = storage
        self._path = path

    def read(self, offset: int, nbytes: int) -> bytes:
        data = self._storage.read_range(self._path, offset, nbytes)
        return b"" if data is None else data

    def read_into(self, offset: int, view) -> int:
        mv = _as_u8(memoryview(view))
        data = self.read(offset, mv.nbytes)
        n = min(len(data), mv.nbytes)
        mv[:n] = data[:n]
        return n

    def size(self) -> Optional[int]:
        data = self._storage.read_bytes(self._path)
        return None if data is None else len(data)

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False


class _PosixRangeReader(RangeReader):
    """Shared-fd pread over the file's parts (one, unless it was written
    under a file-size limit: ``common/fsutil.py``)."""

    def __init__(self, path: str):
        self._fds = [os.open(path, os.O_RDONLY)]
        try:
            for part in fsutil.existing_parts(path)[1:]:
                self._fds.append(os.open(part, os.O_RDONLY))
            sizes = [os.fstat(fd).st_size for fd in self._fds]
        except OSError:
            self.close()
            raise
        self._size = sum(sizes)
        # Every part but the last has the first one's size.
        self._part = sizes[0] if len(sizes) > 1 else sys.maxsize

    def read(self, offset: int, nbytes: int) -> bytes:
        pieces = []
        while nbytes > 0:
            index, at = divmod(offset, self._part)
            if index >= len(self._fds):
                break
            data = os.pread(
                self._fds[index], min(nbytes, self._part - at), at
            )
            if not data:
                break
            pieces.append(data)
            offset += len(data)
            nbytes -= len(data)
        return b"".join(pieces)

    def read_into(self, offset: int, view) -> int:
        mv = _as_u8(memoryview(view))
        total = 0
        while mv.nbytes:
            index, at = divmod(offset, self._part)
            if index >= len(self._fds):
                break
            n = os.preadv(
                self._fds[index], [mv[:self._part - at]], at
            )
            if n == 0:
                break
            total += n
            offset += n
            mv = mv[n:]
        return total

    def size(self) -> int:
        return self._size

    def close(self) -> None:
        for fd in self._fds:
            os.close(fd)
        self._fds = []


class CheckpointStorage(ABC):
    @abstractmethod
    def write(self, content, path: str):
        ...

    @abstractmethod
    def write_bytes(self, data: bytes, path: str):
        ...

    @abstractmethod
    def read(self, path: str, mode: str = "r"):
        ...

    @abstractmethod
    def read_bytes(self, path: str) -> bytes:
        ...

    def read_range(self, path: str, offset: int, nbytes: int):
        """Read `nbytes` starting at `offset`.

        The default falls back to a whole-file read — O(filesize) PER
        BLOCK during sharded restore. Real backends (object stores, ...)
        should override with a native range read.
        """
        data = self.read_bytes(path)
        if data is None:
            return None
        return data[offset:offset + nbytes]

    def open_writer(self, path: str, size: Optional[int] = None) -> StripeWriter:
        """A positional writer whose ``commit`` publishes `path` atomically."""
        return StripeWriter(self, path, size)

    def open_reader(self, path: str) -> Optional[RangeReader]:
        """A positional reader for `path`, or None when it doesn't exist."""
        if not self.exists(path):
            return None
        return RangeReader(self, path)

    def write_chunks(self, chunks, path: str):
        """Write an iterable of bytes-like chunks as one file (atomic).

        Streams through :meth:`open_writer` in scatter-gather batches —
        the chunk iterable is never joined into one contiguous copy of
        the whole checkpoint.
        """
        with self.open_writer(path) as w:
            offset = 0
            batch: List[memoryview] = []
            batch_off = 0
            batch_bytes = 0
            for c in chunks:
                mv = _as_u8(c)
                batch.append(mv)
                batch_bytes += mv.nbytes
                offset += mv.nbytes
                if batch_bytes >= (4 << 20) or len(batch) >= _IOV_MAX:
                    w.writev_at(batch_off, batch)
                    batch, batch_off, batch_bytes = [], offset, 0
            if batch:
                w.writev_at(batch_off, batch)

    @abstractmethod
    def safe_rename(self, src: str, dst: str):
        ...

    @abstractmethod
    def safe_makedirs(self, path: str):
        ...

    @abstractmethod
    def safe_remove(self, path: str):
        ...

    @abstractmethod
    def exists(self, path: str) -> bool:
        ...

    @abstractmethod
    def listdir(self, path: str):
        ...

    def commit(self, step: int, success: bool):
        """Hook called after a full step's shards are persisted."""


class PosixDiskStorage(CheckpointStorage):
    def write(self, content, path: str):
        mode = "wb" if isinstance(content, (bytes, bytearray, memoryview)) else "w"
        tmp = path + ".tmp"
        with open(tmp, mode) as f:
            f.write(content)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    # Binary files go through the positional writer and reader, which
    # keep one larger than the file-size limit as parts.
    def write_bytes(self, data: bytes, path: str):
        with self.open_writer(path, len(data)) as w:
            w.write_at(0, data)

    # read/read_range open and catch instead of pre-checking existence:
    # the exists() probe was both an extra syscall per block and a TOCTOU
    # race against concurrent gc/quarantine renames.
    def read(self, path: str, mode: str = "r"):
        try:
            with open(path, mode) as f:
                return f.read()
        except (FileNotFoundError, NotADirectoryError):
            return None

    def read_bytes(self, path: str) -> Optional[bytes]:
        reader = self.open_reader(path)
        if reader is None:
            return None
        with reader:
            return reader.read(0, reader.size())

    def read_range(self, path: str, offset: int, nbytes: int):
        reader = self.open_reader(path)
        if reader is None:
            return None
        with reader:
            return reader.read(offset, nbytes)

    def open_writer(self, path: str, size: Optional[int] = None) -> StripeWriter:
        return _PosixStripeWriter(path, size)

    def open_reader(self, path: str) -> Optional[RangeReader]:
        try:
            return _PosixRangeReader(path)
        except (FileNotFoundError, NotADirectoryError, IsADirectoryError):
            return None

    def safe_rename(self, src: str, dst: str):
        parts = fsutil.existing_parts(src)[1:]
        os.replace(src, dst)
        for i, part in enumerate(parts, start=1):
            os.replace(part, fsutil.part_path(dst, i))

    def safe_makedirs(self, path: str):
        os.makedirs(path, exist_ok=True)

    def safe_remove(self, path: str):
        if os.path.isdir(path):
            shutil.rmtree(path, ignore_errors=True)
        elif os.path.exists(path):
            try:
                fsutil.remove_parts(path)
            except OSError:
                pass

    def exists(self, path: str) -> bool:
        return os.path.exists(path)

    def listdir(self, path: str):
        if not os.path.isdir(path):
            return []
        return sorted(os.listdir(path))


class CountingStorage(CheckpointStorage):
    """Delegating wrapper that accounts bytes crossing the storage boundary.

    ``read_bytes_total`` / ``write_bytes_total`` sum every read and write
    issued through the wrapper, including positional reader/writer traffic.
    Used by tests and the dedup bench to prove the replica-dedup contracts
    at the only layer that can't lie about them: non-elected replicas write
    zero bytes per checkpoint, and broadcast restore reads each persisted
    byte once instead of once per replica.
    """

    def __init__(self, base: CheckpointStorage):
        self.base = base
        self._lock = threading.Lock()
        self.read_bytes_total = 0
        self.write_bytes_total = 0

    def reset_counts(self):
        with self._lock:
            self.read_bytes_total = 0
            self.write_bytes_total = 0

    def _add_read(self, n: int):
        with self._lock:
            self.read_bytes_total += int(n)

    def _add_write(self, n: int):
        with self._lock:
            self.write_bytes_total += int(n)

    # -- writes --
    def write(self, content, path: str):
        if isinstance(content, (bytes, bytearray, memoryview)):
            self._add_write(len(content))
        else:
            self._add_write(len(str(content)))
        self.base.write(content, path)

    def write_bytes(self, data: bytes, path: str):
        self._add_write(len(data))
        self.base.write_bytes(data, path)

    def open_writer(self, path: str, size: Optional[int] = None) -> StripeWriter:
        outer = self

        base_writer = self.base.open_writer(path, size)

        class _W:
            def __enter__(self):
                base_writer.__enter__()
                return self

            def __exit__(self, *exc):
                return base_writer.__exit__(*exc)

            def write_at(self, offset, data):
                outer._add_write(_as_u8(data).nbytes)
                return base_writer.write_at(offset, data)

            def writev_at(self, offset, views):
                views = [_as_u8(v) for v in views]
                outer._add_write(sum(v.nbytes for v in views))
                return base_writer.writev_at(offset, views)

            def commit(self):
                base_writer.commit()

            def abort(self):
                base_writer.abort()

        return _W()

    # -- reads --
    def read(self, path: str, mode: str = "r"):
        data = self.base.read(path, mode)
        if data is not None:
            self._add_read(len(data))
        return data

    def read_bytes(self, path: str) -> bytes:
        data = self.base.read_bytes(path)
        if data is not None:
            self._add_read(len(data))
        return data

    def read_range(self, path: str, offset: int, nbytes: int):
        data = self.base.read_range(path, offset, nbytes)
        if data is not None:
            self._add_read(len(data))
        return data

    def open_reader(self, path: str) -> Optional[RangeReader]:
        base_reader = self.base.open_reader(path)
        if base_reader is None:
            return None
        outer = self

        class _R:
            def read(self, offset, nbytes):
                data = base_reader.read(offset, nbytes)
                outer._add_read(len(data))
                return data

            def read_into(self, offset, view):
                got = base_reader.read_into(offset, view)
                outer._add_read(got)
                return got

            def size(self):
                return base_reader.size()

            def close(self):
                base_reader.close()

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                self.close()
                return False

        return _R()

    # -- passthrough --
    def safe_rename(self, src: str, dst: str):
        self.base.safe_rename(src, dst)

    def safe_makedirs(self, path: str):
        self.base.safe_makedirs(path)

    def safe_remove(self, path: str):
        self.base.safe_remove(path)

    def exists(self, path: str) -> bool:
        return self.base.exists(path)

    def listdir(self, path: str):
        return self.base.listdir(path)

    def commit(self, step: int, success: bool):
        self.base.commit(step, success)


def get_checkpoint_storage(storage: Optional[CheckpointStorage] = None):
    storage = storage or PosixDiskStorage()
    # Lazy import: chaos.storage imports this module at load time, and
    # chaos stays entirely out of the way unless the env arms a plan.
    from dlrover_tpu.chaos.storage import maybe_chaos_storage

    return maybe_chaos_storage(storage)
