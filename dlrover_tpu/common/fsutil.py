"""Atomic small-file writes: tmp + fsync + ``os.replace``.

The commit protocol every durable artifact in this codebase uses (state
snapshots, trackers, trace exports, result files): write the full
payload to a same-directory temp file, fsync it, then ``os.replace``
onto the final name. Readers therefore see either the old complete file
or the new complete file, never a torn one — the invariant dtlint DT005
enforces for durable-state modules.

Same-directory matters twice: ``os.replace`` must not cross a
filesystem boundary, and the rename is only durable once the *directory*
is synced, which callers that need directory durability do themselves
(the state store does; one-shot result files don't bother).
"""

import mmap
import os
import resource
import sys
import tempfile
from typing import List, Union


def atomic_write_bytes(path: str, data: bytes, fsync: bool = True) -> None:
    """Atomically replace `path` with `data` (tmp+fsync+replace)."""
    dirname = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(
        prefix=os.path.basename(path) + ".", suffix=".tmp", dir=dirname
    )
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
            if fsync:
                f.flush()
                os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        # Never leave a stray tmp on the durable path (GC trusts the
        # directory contents); the original file is untouched.
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def atomic_write_text(
    path: str, data: str, encoding: str = "utf-8", fsync: bool = True
) -> None:
    atomic_write_bytes(path, data.encode(encoding), fsync=fsync)


def write_or_none(path: str) -> Union[bytes, None]:
    """Open-and-catch read: the file's bytes, or None if it does not
    exist (the race-free replacement for exists-then-open)."""
    try:
        with open(path, "rb") as f:
            return f.read()
    except (FileNotFoundError, IsADirectoryError):
        return None


# -- files larger than the process may write -------------------------------
#
# Under a file-size limit (``RLIMIT_FSIZE``: ``ulimit -f``, a container's
# rlimits) no single file may pass the limit: ``ftruncate`` and ``write``
# fail with EFBIG. The two places that write files as large as the training
# state — the flash-checkpoint shm segment and the persisted shard — then
# store one logical file as parts: ``path``, ``path.part1``, ``path.part2``…
# Every part but the last has the size of the first, so a reader needs no
# other record, and a reader under another limit (or none) reads the same
# bytes. Without a limit there is one part and the layout is what it
# always was.


def max_part_bytes() -> int:
    """The most bytes one file may hold here: page-aligned and strictly
    below the soft ``RLIMIT_FSIZE`` (Linux refuses a length above the
    limit, gVisor one equal to it); ``sys.maxsize`` when unlimited."""
    soft, _ = resource.getrlimit(resource.RLIMIT_FSIZE)
    if soft == resource.RLIM_INFINITY:
        return sys.maxsize
    part = (soft - 1) // mmap.PAGESIZE * mmap.PAGESIZE
    if part <= 0:
        raise OSError(
            f"RLIMIT_FSIZE is {soft} bytes: no file of a page can be written"
        )
    return part


def part_path(path: str, index: int) -> str:
    return path if index == 0 else f"{path}.part{index}"


def existing_parts(path: str) -> List[str]:
    """The part files of `path` that exist, in order; empty when `path`
    itself does not."""
    parts: List[str] = []
    while os.path.exists(part_path(path, len(parts))):
        parts.append(part_path(path, len(parts)))
    return parts


def remove_parts(path: str, start: int = 0) -> None:
    """Remove the parts of `path` from index `start` up. A missing part 0
    does not end the search: a torn removal may have left later ones."""
    index = start
    while True:
        try:
            os.unlink(part_path(path, index))
        except FileNotFoundError:
            if index > 0:
                return
        index += 1
