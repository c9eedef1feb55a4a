"""Trainer-side flash-checkpoint engine.

Parity: reference ``dlrover/trainer/torch/flash_checkpoint/engine.py:47-304``
(shm staging, readiness/step-consistency, memory/disk paths) merged with the
shm-handler half of ``dlrover/python/elastic_agent/torch/ckpt_saver.py:171-291``
(TensorMeta layout + buffer traversal) and the one-shard-per-rank design of
``fsdp_engine.py:158-224``, rebuilt for JAX:

- the state dict is any JAX pytree; array leaves are staged into a POSIX shm
  buffer, scalar/python leaves ride in the meta record;
- GSPMD-sharded leaves stage only this process's *addressable* blocks
  (deduplicated by shard index); the globally replica-0 copy of each block
  is marked for disk persist, so a sharded state stores each byte exactly
  once across processes and restore can re-assemble it for any new mesh;
- **asynchronous saves are donation-safe**: ``save_to_memory_async``
  dispatches engine-owned device→host copies into XLA's ``pinned_host``
  memory space and returns in milliseconds; the
  runtime orders those copies before any later donated step reuses the
  buffers, so the background fetch never races training;
- in **agent mode** (launched under `dlrover-tpu-run`) the engine registers a
  saver with the agent over the factory queue and persists via save events —
  `save_to_memory` returns in milliseconds and the agent owns disk I/O and
  crash flushes;
- in **standalone mode** (no agent) persists inline with the same two-phase
  commit, so the file format is identical either way.
"""

import dataclasses
import os
import pickle
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from dlrover_tpu.chaos.injector import fault_hit
from dlrover_tpu.chaos.sites import ChaosSite
from dlrover_tpu.common import checksum, ckpt_persist, fastcopy
from dlrover_tpu.common.ckpt_meta import (
    SaveEvent,
    SaverRegistration,
    ShardMeta,
    TensorMeta,
    ckpt_event_queue,
    ckpt_factory_queue,
    ckpt_lock_name,
    ckpt_meta_dict,
    ckpt_shm_name,
)
from dlrover_tpu.common.comm import (
    SharedDict,
    SharedLock,
    SharedQueue,
    server_exists,
)
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import logger
from dlrover_tpu.common.shared_memory import SharedMemory
from dlrover_tpu.common.storage import CheckpointStorage, get_checkpoint_storage
from dlrover_tpu.observability.events import EventKind, emit
from dlrover_tpu.utils.tracing import get_tracer

_ALIGN = 128  # bytes; keeps row-major copies cache-line aligned


def _aligned(n: int) -> int:
    return (n + _ALIGN - 1) // _ALIGN * _ALIGN


def _flatten_state(state) -> Tuple[List[Tuple[str, Any]], Dict[str, Any]]:
    """Split a pytree into (path, array) leaves and non-array objects.

    Paths are ``jax.tree_util.keystr`` strings — deterministic for a given
    tree structure, so a template flattened the same way yields the same keys.
    """
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(state)
    arrays: List[Tuple[str, Any]] = []
    objects: Dict[str, Any] = {}
    for kp, leaf in leaves:
        path = jax.tree_util.keystr(kp)
        if isinstance(leaf, (jax.Array, np.ndarray, np.generic)):
            arrays.append((path, leaf))
        else:
            objects[path] = leaf
    return arrays, objects


def _index_key(index, shape) -> Tuple[Tuple[int, int], ...]:
    """Normalize a shard's slice-tuple index to ((start, stop), ...)."""
    out = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append((start, stop))
    return tuple(out)


def _memo_reader(read: Callable[[], np.ndarray]) -> Callable[[], np.ndarray]:
    """Cache a block reader's result for the duration of one leaf rebuild."""
    cache: List[np.ndarray] = []

    def cached() -> np.ndarray:
        if not cache:
            cache.append(read())
        return cache[0]

    # Forward the direct-into fast path: exact-match destinations pread
    # straight into the preallocated view and never need the memo.
    read_into = getattr(read, "read_into", None)
    if read_into is not None:
        cached.read_into = read_into
    return cached


class _CountingReader:
    """Delegating reader that accounts storage bytes into restore stats.

    Broadcast restore's contract — survivors hydrate device-to-device
    instead of each hammering storage — is only checkable if the bytes a
    restore actually pread are measured at the reader boundary; tests and
    the dedup bench assert on ``last_restore_stats["storage_read_bytes"]``.
    Counter updates are lock-guarded: block reads run on the fastcopy pool.
    """

    def __init__(self, base, stats: Dict[str, Any]):
        import threading

        self._base = base
        self._stats = stats
        self._lock = threading.Lock()

    def _count(self, n: int):
        with self._lock:
            self._stats["storage_read_bytes"] = (
                self._stats.get("storage_read_bytes", 0) + int(n)
            )

    def read(self, offset: int, nbytes: int) -> bytes:
        data = self._base.read(offset, nbytes)
        self._count(len(data))
        return data

    def read_into(self, offset: int, view) -> int:
        got = self._base.read_into(offset, view)
        self._count(got)
        return got

    def size(self) -> int:
        return self._base.size()

    def close(self):
        self._base.close()


@dataclasses.dataclass
class _Block:
    """One staged block in flight: metadata + an engine-owned data handle."""

    path: str
    index: Optional[Tuple[Tuple[int, int], ...]]  # None => whole array
    global_shape: Optional[Tuple[int, ...]]
    persist: bool
    handle: Any  # jax.Array (engine-owned copy) or np.ndarray


class CheckpointEngine:
    """Stage one process's checkpoint shard into shared memory.

    One engine per training process; ``global_shard_id``/``global_shard_num``
    name this process's shard in the global checkpoint (for a replicated
    state dict, rank 0 uses 1 shard; for a sharded state each process is a
    shard — the DDP vs FSDP/Megatron saver split of the reference,
    ``ckpt_saver.py:979-1029``).
    """

    def __init__(
        self,
        checkpoint_dir: str,
        global_shard_id: int = 0,
        global_shard_num: int = 1,
        persist_shard: bool = True,
        storage: Optional[CheckpointStorage] = None,
        keep_latest: int = 3,
        job: str = "",
        zero_degree: int = 0,
        replica_rank: int = 0,
        replica_count: int = 1,
        mesh_axes: Optional[Dict[str, int]] = None,
    ):
        # Warm the copy engine off the critical path: the first snapshot
        # must not stall behind a toolchain build or calibration.
        fastcopy.prime()
        self.checkpoint_dir = checkpoint_dir
        self.global_shard_id = global_shard_id
        self.global_shard_num = global_shard_num
        # Every process stages to its own shm (so memory restore is local);
        # only processes with persist_shard=True own a disk shard.
        self.persist_shard = persist_shard
        # Replica-dedup: when `replica_count` > 1 this engine's shard is a
        # data-parallel replica of `replica_count` identical copies and only
        # the *elected* writer persists it (master-journaled first-claimant
        # election; deterministic replica-0 fallback without a master) —
        # the fleet writes each replicated byte once instead of Ndp times.
        self.replica_rank = int(replica_rank)
        self.replica_count = int(replica_count)
        self._writer_owner: Optional[int] = None
        # ZeRO-1 degree the optimizer state is sharded over (0 = replicated).
        # Stamped into every ShardMeta so restore can name both degrees when
        # a checkpoint saved under a different data degree can't be re-sliced.
        self.zero_degree = int(zero_degree)
        # Mesh axes this engine saves under (e.g. {"data": 4}); diagnostic
        # context for cross-topology restore errors.
        self.mesh_axes = dict(mesh_axes) if mesh_axes else None
        self.storage = get_checkpoint_storage(storage)
        self.keep_latest = keep_latest
        self._job = job or os.getenv(NodeEnv.JOB_NAME, "local-job")
        self._local_rank = int(os.getenv(NodeEnv.LOCAL_RANK, "0"))
        self._node_rank = int(os.getenv(NodeEnv.NODE_RANK, "0"))
        self._local_world = int(os.getenv(NodeEnv.LOCAL_WORLD_SIZE, "1"))
        self._world_size = int(os.getenv(NodeEnv.NUM_PROCESSES, "1"))
        self._rank = int(os.getenv(NodeEnv.PROCESS_ID, "0"))

        self._shm: Optional[SharedMemory] = None
        self._shm_name = ckpt_shm_name(
            self._job, self._node_rank, self._local_rank
        )
        self._layout_version = 0
        self._cached_step = -1
        #: Memory space the last async snapshot's engine-owned copies
        #: landed in, as the runtime reports it (None before the first).
        self.staging_memory_kind: Optional[str] = None
        # Async staging: one background writer, at most one snapshot in
        # flight (a newer request while busy is skipped, not queued).
        import concurrent.futures
        import threading

        self._stage_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="ckpt-stage"
        )
        self._staging = None
        # Write ordering: every snapshot request takes a generation number;
        # the buffer write + meta publish happen under _write_mutex and a
        # request superseded by a newer one is dropped. This keeps a stalled
        # async staging from landing a stale step over a newer sync save
        # (and from tearing the buffer under it).
        self._write_mutex = threading.Lock()
        self._gen_lock = threading.Lock()
        self._next_gen = 0
        self._done_gen = 0

        self.agent_mode = server_exists(
            "queue", ckpt_factory_queue(self._node_rank), self._job
        )
        if self.agent_mode:
            self._register_with_agent()
            self._lock = SharedLock(
                ckpt_lock_name(self._node_rank, self._local_rank),
                create=False, job=self._job,
            )
            self._meta = SharedDict(
                ckpt_meta_dict(self._node_rank), create=False, job=self._job
            )
            self._events = SharedQueue(
                ckpt_event_queue(self._node_rank), create=False, job=self._job
            )
            logger.info(
                "checkpoint engine in agent mode (shard %s/%s, shm %s)",
                global_shard_id, global_shard_num, self._shm_name,
            )
        else:
            self._lock = None
            self._meta_local: Dict[str, bytes] = {}
            logger.info(
                "checkpoint engine in standalone mode (shard %s/%s)",
                global_shard_id, global_shard_num,
            )

    # ------------- agent handshake -------------
    def _register_with_agent(self):
        factory = SharedQueue(
            ckpt_factory_queue(self._node_rank), create=False, job=self._job
        )
        factory.put(
            SaverRegistration(
                class_name="CommonDirCheckpointSaver",
                checkpoint_dir=self.checkpoint_dir,
                local_shard_num=self._local_world,
                global_shard_num=self.global_shard_num,
                node_rank=self._node_rank,
                is_committer=self._node_rank == 0,
                keep_latest=self.keep_latest,
            )
        )

    # ------------- staging -------------
    def _snapshot(self, state, own: bool,
                  step: int = -1) -> Tuple[List[_Block], Dict]:
        """Decompose `state` into staged blocks (dispatch-only, no host sync).

        A GSPMD leaf contributes one block per unique addressable shard
        index; ``persist`` marks blocks whose replica-0 copy lives on this
        process. With ``own=True`` every device block is snapshotted into an
        engine-owned array in the host memory space: the XLA runtime orders
        those copies before any later donated execution overwrites the
        source buffers, which is what makes the async path safe against
        ``donate_argnums`` training steps. ``own=False`` skips the copy for
        synchronous saves that fetch before returning.
        """
        with get_tracer().span("ckpt.snapshot", step=step):
            return self._snapshot_blocks(state, own)

    def _snapshot_blocks(self, state, own: bool) -> Tuple[List[_Block], Dict]:
        import jax

        arrays, objects = _flatten_state(state)
        blocks: List[_Block] = []
        device_data: List[Any] = []
        device_slots: List[int] = []
        for path, leaf in arrays:
            if not isinstance(leaf, jax.Array):
                host = np.asarray(leaf)
                if own:
                    # The caller may mutate host arrays after an async
                    # dispatch returns; snapshot them now.
                    host = host.copy()
                blocks.append(_Block(path, None, None, True, host))
                continue
            uniq: Dict[Tuple, List] = {}
            for sh in leaf.addressable_shards:
                key = _index_key(sh.index, leaf.shape)
                ent = uniq.get(key)
                if ent is None:
                    uniq[key] = ent = [False, sh.data]
                if sh.replica_id == 0:
                    ent[0] = True
            full = tuple((0, int(d)) for d in leaf.shape)
            whole = len(uniq) == 1 and next(iter(uniq)) == full
            if self.global_shard_num == 1 and self.persist_shard:
                # Replicated layout (FlashCheckpointer): this process IS
                # the one disk shard — persist all its blocks even when the
                # mesh's device order gives its replicas nonzero ids
                # (replica-0 dedup only applies to multi-shard layouts).
                for ent in uniq.values():
                    ent[0] = True
            for key, (persist, data) in uniq.items():
                blocks.append(
                    _Block(
                        path,
                        None if whole else key,
                        None if whole else tuple(int(d) for d in leaf.shape),
                        persist,
                        data,
                    )
                )
                device_data.append(data)
                device_slots.append(len(blocks) - 1)
        if own and device_data:
            owned = self._own_copies(device_data)
            for slot, arr in zip(device_slots, owned):
                blocks[slot].handle = arr
        return blocks, objects

    def _own_copies(self, arrs: List[Any]) -> List[Any]:
        """Dispatch engine-owned copies of single-device arrays (async):
        one batched ``device_put`` into the host memory space
        (``pinned_host``) — zero extra HBM, the D2H DMA overlaps whatever
        runs next, and the result's lifetime is independent of the
        caller's arrays, so later donation cannot invalidate the
        snapshot. There is no on-device fallback: a second copy of the
        state in HBM is exactly what a memory-filling job cannot afford,
        so a backend that refuses the host space fails the save loudly.
        """
        import jax

        with get_tracer().span(
            "ckpt.own_copies", bytes=sum(int(a.nbytes) for a in arrs)
        ):
            shardings = [
                jax.sharding.SingleDeviceSharding(
                    list(a.devices())[0], memory_kind="pinned_host"
                )
                for a in arrs
            ]
            owned = jax.device_put(arrs, shardings)
        self.staging_memory_kind = owned[0].sharding.memory_kind
        return owned

    # Target bytes per device_get batch on the staging path. One giant
    # batched fetch serializes the whole D2H on a single transfer;
    # chunking lets the fastcopy pool overlap transfers and bounds peak
    # scratch-host memory. The benchmark reads the rate as
    # ``ckpt.stage_gbps`` and the fetch's time as ``ckpt.fetch_s``.
    _STAGE_CHUNK_BYTES = 32 << 20

    def _fetch(self, blocks: List[_Block],
               step: int = -1) -> List[np.ndarray]:
        """Complete the device→host fetch for every block, release the
        engine-owned handles, and return host arrays aligned with `blocks`.

        Device blocks are fetched in ~``_STAGE_CHUNK_BYTES`` groups through
        the shared fastcopy pool so independent transfers overlap instead of
        riding one serialized ``device_get``; every staging emits a
        ``ckpt.io`` event with ``op="staging"`` so D2H throughput is
        attributable per save (its time is the ``ckpt.fetch`` span's)."""
        with get_tracer().span("ckpt.fetch", step=step) as span:
            out, staged_bytes, chunks = self._fetch_blocks(blocks)
            span.args.update(bytes=staged_bytes, chunks=chunks)
        if staged_bytes:
            wall = span.duration_s
            emit(
                EventKind.CKPT_IO, op="staging", step=step,
                bytes=staged_bytes,
                mbps=round(staged_bytes / max(wall, 1e-9) / 1e6, 1),
                duration_s=round(wall, 4), chunks=chunks,
            )
        return out

    def _fetch_blocks(
        self, blocks: List[_Block]
    ) -> Tuple[List[np.ndarray], int, int]:
        """(host arrays, bytes fetched from device blocks, chunks)."""
        import jax

        device_idx = [
            i for i, b in enumerate(blocks) if isinstance(b.handle, jax.Array)
        ]
        groups: List[List[int]] = []
        cur: List[int] = []
        cur_bytes = 0
        for i in device_idx:
            cur.append(i)
            cur_bytes += int(blocks[i].handle.nbytes)
            if cur_bytes >= self._STAGE_CHUNK_BYTES:
                groups.append(cur)
                cur, cur_bytes = [], 0
        if cur:
            groups.append(cur)

        def _get(idxs: List[int]):
            return idxs, jax.device_get([blocks[i].handle for i in idxs])

        by_slot: Dict[int, Any] = {}
        for idxs, fetched in fastcopy.parallel_map(_get, groups):
            for i, arr in zip(idxs, fetched):
                by_slot[i] = arr
        out: List[np.ndarray] = []
        staged_bytes = 0
        for i, b in enumerate(blocks):
            arr = by_slot.get(i)
            if arr is None:
                arr = np.asarray(b.handle)
            host = np.asarray(arr)
            out.append(host)
            if i in by_slot:
                staged_bytes += host.nbytes
            b.handle = None  # free the device/host-space copy eagerly
        return out, int(staged_bytes), len(groups)

    def _layout(
        self, blocks: List[_Block], host_arrays: List[np.ndarray]
    ) -> Tuple[List[TensorMeta], int]:
        metas, offset = [], 0
        for b, arr in zip(blocks, host_arrays):
            nbytes = arr.nbytes
            metas.append(
                TensorMeta(
                    path=b.path, offset=offset, nbytes=nbytes,
                    dtype=str(arr.dtype), shape=tuple(arr.shape),
                    global_shape=b.global_shape, index=b.index,
                    persist=b.persist,
                )
            )
            offset += _aligned(nbytes)
        return metas, offset

    def _ensure_shm(self, needed: int):
        if self._shm is not None and self._shm.size >= needed:
            return
        if self._shm is None and SharedMemory.exists(self._shm_name):
            try:
                existing = SharedMemory(self._shm_name)
                if existing.size >= needed:
                    self._shm = existing
                    return
                existing.close()
            except (ValueError, OSError):
                pass
        if self._shm is not None:
            self._shm.close()
        # Slack so steady-state training never recreates the segment.
        size = _aligned(int(needed * 1.1) + 4096)
        SharedMemory.remove(self._shm_name)
        self._shm = SharedMemory(self._shm_name, create=True, size=size)
        self._layout_version += 1
        logger.info(
            "created checkpoint shm %s (%.1f MB)",
            self._shm_name, size / 1e6,
        )

    def save_to_memory(self, step: int, state, block: bool = False) -> bool:
        """Stage `state` into the shm buffer synchronously. With
        ``block=False`` (the MEMORY fast path) returns False when the saver
        is persisting this buffer right now — a skipped snapshot is cheaper
        than a stalled step (parity with the reference's skip-on-contention,
        ``engine.py:272``). DISK saves pass ``block=True`` so a requested
        persist is never lost to brief lock contention."""
        gen = self._take_gen()
        blocks, objects = self._snapshot(state, own=False, step=step)
        host_arrays = self._fetch(blocks, step)
        return self._write_snapshot(
            step, blocks, host_arrays, objects, block, gen
        )

    def save_to_memory_async(self, step: int, state) -> bool:
        """Non-blocking memory snapshot: dispatch engine-owned D2H copies
        and return immediately; a background thread finishes the fetch and
        the shm write. This is the TPU-first answer to the reference's
        blocking-save design — the dispatched copies are ordered by the
        runtime before any later donated step reuses the buffers, so the
        snapshot is consistent even when training runs ahead through a
        ``donate_argnums`` train step, and the blocking cost is just the
        dispatch (~ms) instead of D2H + memcpy.

        Returns False (snapshot skipped) while a previous staging is still
        in flight — same semantics as a lock-contention skip. The comms
        governor can also skip a step's staging while the master flags
        the host link saturated (the D2H fetch is exactly the traffic
        contending with the step's collectives); the deferral is bounded
        by DLROVER_TPU_COMMS_DEFER_MAX_STEPS and surfaced as a
        ``ckpt.io`` event with ``op="staging-defer"``.
        """
        if self._staging is not None and not self._staging.done():
            get_tracer().count("ckpt.skipped", reason="staging_in_flight")
            return False
        from dlrover_tpu.train.comms import get_governor

        governor = get_governor()
        if governor is not None and not governor.allow_staging(step):
            get_tracer().count("ckpt.skipped", reason="governor")
            emit(EventKind.CKPT_IO, op="staging-defer", step=step, bytes=0)
            return False
        gen = self._take_gen()
        blocks, objects = self._snapshot(state, own=True, step=step)
        self._staging = self._stage_pool.submit(
            self._stage_async, step, blocks, objects, gen
        )
        return True

    def _stage_async(self, step, blocks, objects, gen):
        try:
            with get_tracer().span("ckpt.stage", step=step) as span:
                host_arrays = self._fetch(blocks, step)
                span.args["bytes"] = sum(int(a.nbytes) for a in host_arrays)
                ok = self._write_snapshot(
                    step, blocks, host_arrays, objects, True, gen
                )
        except Exception:
            # The future is often never awaited — a silent raise here would
            # turn every crash-restore guarantee into a lie. Log loudly.
            logger.exception(
                "async memory snapshot of step %s FAILED to stage", step
            )
            return False
        if not ok:
            # Make the drop observable: an async save that returned True at
            # dispatch did NOT land (lock contention or superseded).
            logger.warning(
                "async memory snapshot of step %s was not staged", step
            )
        return ok

    def wait_staged(self, timeout: float = 600.0) -> bool:
        """Join an in-flight async staging (no-op when none pending)."""
        if self._staging is None:
            return True
        try:
            return bool(self._staging.result(timeout=timeout))
        except Exception:
            logger.exception("async checkpoint staging failed")
            return False

    def _take_gen(self) -> int:
        with self._gen_lock:
            self._next_gen += 1
            return self._next_gen

    def _superseded(self, gen: int) -> bool:
        with self._gen_lock:
            return gen <= self._done_gen

    def _write_snapshot(self, step, blocks, host_arrays, objects,
                        block: bool, gen: Optional[int] = None) -> bool:
        if gen is None:
            gen = self._take_gen()
        tracer = get_tracer()
        with tracer.span("ckpt.lock_wait", step=step):
            dropped = self._take_write_locks(gen, block)
        if dropped:
            tracer.count("ckpt.skipped", reason=dropped)
            if dropped == "superseded":
                logger.info(
                    "memory snapshot of step %s superseded; dropped", step
                )
            else:
                logger.warning(
                    "skip memory save at step %s: saver holds the shard "
                    "lock", step,
                )
            return False
        try:
            with tracer.span("ckpt.shm_copy", step=step) as span:
                metas, used = self._layout(blocks, host_arrays)
                span.args["bytes"] = used
                self._ensure_shm(used)
                buf = self._shm.buf
                pairs = []
                for meta, arr in zip(metas, host_arrays):
                    dst = np.ndarray(
                        (meta.nbytes,), dtype=np.uint8, buffer=buf,
                        offset=meta.offset,
                    )
                    pairs.append((dst, fastcopy.as_bytes_view(arr)))
                fastcopy.copy_many(pairs)
            with tracer.span("ckpt.shm_flush", step=step):
                self._shm.flush()
            with tracer.span("ckpt.publish", step=step):
                shard_meta = ShardMeta(
                    step=step,
                    shm_name=self._shm_name,
                    used_bytes=used,
                    tensors=metas,
                    objects=objects,
                    global_shard_id=self.global_shard_id,
                    global_shard_num=self.global_shard_num,
                    # Election-gated: the agent saver persists every local
                    # shard whose meta says persist, so a non-elected
                    # replica must publish False or the fleet re-gains the
                    # Ndp× write amplification through the agent path.
                    persist=self._persist_owner(),
                    layout_version=self._layout_version,
                    zero_degree=self.zero_degree,
                    mesh_axes=self.mesh_axes,
                )
                self._publish_meta(shard_meta)
                self._cached_step = step
            with self._gen_lock:
                self._done_gen = max(self._done_gen, gen)
            return True
        finally:
            if self._lock is not None:
                self._lock.release()
            self._write_mutex.release()

    def _take_write_locks(self, gen: int, block: bool) -> str:
        """Serialize buffer writers: take the write mutex, then the shard
        lock shared with the agent's saver. Returns "" with both held, or
        — holding neither — why the snapshot is dropped: ``superseded`` (it
        lost the race to a newer request and must not land stale data
        over it) or ``lock`` (the saver is persisting this buffer)."""
        self._write_mutex.acquire()
        try:
            if self._superseded(gen):
                dropped = "superseded"
            elif self._lock is not None and not self._lock.acquire(
                blocking=block, timeout=30.0 if block else -1
            ):
                dropped = "lock"
            else:
                return ""
        except BaseException:
            self._write_mutex.release()
            raise
        self._write_mutex.release()
        return dropped

    def _publish_meta(self, shard_meta: ShardMeta):
        raw = pickle.dumps(shard_meta)
        if self.agent_mode:
            self._meta.set(f"rank_{self._local_rank}", raw)
        else:
            self._meta_local[f"rank_{self._local_rank}"] = raw

    def save_to_storage(self, step: int, state) -> bool:
        """Memory save + asynchronous (agent) or inline (standalone) persist.

        With data-parallel replicas (``replica_count`` > 1) only the elected
        writer persists; the other replicas stop after the memory stage —
        their snapshot still serves warm restarts, but the fleet writes each
        replicated byte once instead of Ndp times."""
        if not self.save_to_memory(step, state, block=True):
            return False
        if self.agent_mode:
            # Local rank 0 triggers the node's persist; the agent saver
            # persists every persist-owning local shard of this step
            # (parity: ddp_engine.py:102-127).
            if self._local_rank == 0:
                self._events.put(SaveEvent(step=step))
            return True
        if not self._persist_owner():
            if self.persist_shard:
                # An eligible replica skipped by the election — record a
                # zero-byte persist so the per-replica persist-bytes gauge
                # shows the dedup cut, not a gap.
                emit(
                    EventKind.CKPT_IO, op="persist-skip", step=step,
                    bytes=0, written_bytes=0,
                    replica=self.replica_rank,
                    owner=self._writer_owner
                    if self._writer_owner is not None else 0,
                )
            return True
        return self._persist_inline(step)

    def _persist_owner(self) -> bool:
        """Is this replica the disk writer for its shard group?

        One replica (or no replica metadata): the static ``persist_shard``
        flag stands. With data-parallel replicas the master runs a journaled
        first-claimant election per (checkpoint_dir × shard) group and
        restart epoch — the winning rank is durable across master failover
        because the election RPC replays from the WAL and rides in state
        snapshots. Without a master, the lowest replica rank wins, which
        reproduces the classic rank-0-writes behavior deterministically."""
        if not self.persist_shard:
            return False
        if self.replica_count <= 1:
            return True
        if self._writer_owner is None:
            owner = 0
            if os.getenv(NodeEnv.MASTER_ADDR):
                try:
                    from dlrover_tpu.agent.master_client import MasterClient

                    epoch = int(os.getenv(NodeEnv.RESTART_COUNT, "0"))
                    group = (
                        f"{self.checkpoint_dir}:shard{self.global_shard_id}"
                    )
                    lease = MasterClient.singleton_instance().elect_ckpt_writer(
                        group, epoch, self.replica_rank
                    )
                    if lease is not None and lease.exists:
                        owner = lease.owner_rank
                except Exception as e:
                    logger.warning(
                        "checkpoint writer election failed (%s); falling "
                        "back to replica 0 as writer", e,
                    )
            self._writer_owner = owner
            logger.info(
                "checkpoint writer for shard %s is replica %s (this is "
                "replica %s of %s)", self.global_shard_id, owner,
                self.replica_rank, self.replica_count,
            )
        return self._writer_owner == self.replica_rank

    def _persist_inline(self, step: int) -> bool:
        meta = pickle.loads(self._meta_local[f"rank_{self._local_rank}"])
        ckpt_persist.persist_shard(
            self.storage, self.checkpoint_dir, meta, self._shm.buf
        )
        if self.global_shard_id == 0:
            ok = ckpt_persist.commit_step(
                self.storage, self.checkpoint_dir, step,
                self.global_shard_num,
            )
            if ok:
                ckpt_persist.gc_steps(
                    self.storage, self.checkpoint_dir, self.keep_latest
                )
            return ok
        return True

    # ------------- restore -------------
    def _memory_meta(self) -> Optional[ShardMeta]:
        raw = (
            self._meta.get(f"rank_{self._local_rank}")
            if self.agent_mode
            else self._meta_local.get(f"rank_{self._local_rank}")
        )
        if not raw:
            return None
        try:
            return pickle.loads(raw)
        except Exception:
            return None

    def _consistent_memory_step(self, my_step: int) -> bool:
        """All processes must restore the same step; vote via the master
        kv-store (the reference allgathers on a gloo group, ``engine.py:64``)."""
        if self._world_size <= 1 or not os.getenv(NodeEnv.MASTER_ADDR):
            return my_step >= 0
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient.singleton_instance()
        incarnation = os.getenv(NodeEnv.RESTART_COUNT, "0")
        prefix = f"ckpt_vote/{incarnation}"
        client.kv_store_set(f"{prefix}/{self._rank}", str(my_step).encode())
        keys = [f"{prefix}/{r}" for r in range(self._world_size)]
        try:
            votes = client.kv_store_wait(keys, timeout=60.0)
        except TimeoutError:
            logger.warning("checkpoint step vote timed out; using storage")
            return False
        steps = {int(v.decode()) for v in votes.values()}
        return len(steps) == 1 and my_step >= 0

    def load(self, template) -> Tuple[int, Any]:
        """Restore (step, state). Memory snapshot first, storage fallback.

        `template` is a pytree of the same structure (e.g. the freshly
        initialized train state); its leaves define paths, dtypes, shapes
        and — for GSPMD leaves — the target shardings: restore re-assembles
        blocks for the template's mesh, so a checkpoint saved under one
        topology loads under another (reshard-on-restore).
        Returns ``(-1, template)`` when nothing is restorable.

        Per-phase wall times land in ``last_restore_stats``
        (read/assemble/device_put seconds + source + bytes) so slow
        restores are attributable (the reference claims
        seconds-from-shm, ``/root/reference/docs/blogs/flash_checkpoint.md:311``;
        the benchmark reads them as ``ckpt.restore_s`` and its parts).
        """
        self.wait_staged(60.0)
        # Stats cover the restore itself — staging waits and (on
        # fallback) the failed memory attempt are excluded so each
        # phase number means what it says.
        self._reset_restore_stats()
        t_load0 = time.perf_counter()
        chaos = fault_hit(ChaosSite.CKPT_SHM, detail=self._shm_name)
        if chaos is not None and chaos.kind == "lose":
            # Simulate a host reboot that wiped /dev/shm: the warm
            # snapshot is gone and restore must fall back to storage.
            logger.warning(
                "CHAOS: losing shm snapshot %s", self._shm_name
            )
            if self._shm is not None:
                self._shm.close()
                self._shm = None
            SharedMemory.remove(self._shm_name)
        meta = self._memory_meta()
        has_memory = meta is not None and SharedMemory.exists(self._shm_name)
        my_step = meta.step if has_memory else -1
        # Vote unconditionally — a rank with no snapshot must still publish
        # -1, or every other rank blocks the full wait before falling back.
        consistent = self._consistent_memory_step(my_step)
        if has_memory:
            if consistent:
                try:
                    shm = self._shm or SharedMemory(self._shm_name)
                    self._shm = shm
                    buf = shm.buf
                    catalog: Dict[str, List] = {}
                    for t in meta.tensors:
                        catalog.setdefault(t.path, []).append(
                            (t, self._shm_reader(buf, t))
                        )
                    # The write mutex keeps a straggling staging thread from
                    # rewriting the buffer mid-read.
                    with self._write_mutex:
                        state = self._rebuild(template, catalog, meta.objects)
                    self._cached_step = meta.step
                    self._finish_restore_stats(
                        "memory", meta.used_bytes, t_load0
                    )
                    logger.info(
                        "restored step %s from memory snapshot (%s)",
                        meta.step, self._restore_stats,
                    )
                    emit(
                        EventKind.CKPT_RESTORE, source="memory",
                        step=meta.step,
                        duration_s=round(time.perf_counter() - t_load0, 3),
                    )
                    return meta.step, state
                except Exception:
                    logger.exception("memory restore failed; trying storage")
        return self._load_from_storage(template)

    @staticmethod
    def _shm_reader(buf, t: TensorMeta) -> Callable[[], np.ndarray]:
        def read() -> np.ndarray:
            flat = np.ndarray(
                (t.nbytes,), dtype=np.uint8, buffer=buf, offset=t.offset
            )
            return flat.view(t.dtype).reshape(t.shape)

        return read

    def memory_region_reader(self):
        """``(step, read_region)`` over the newest shm snapshot.

        The mesh-reshape hydration path (``train/rescale.py``) pulls most
        of the new layout device-to-device from the surviving shards and
        only needs the snapshot for the regions the dead members held —
        a full ``load()`` would read and re-device_put everything. This
        hands out a targeted reader instead: ``read_region(path, region)``
        assembles exactly that region from the snapshot blocks (region is
        ``((start, stop), ...)`` per axis in global coordinates) and
        raises ``KeyError`` on an unknown path or a cover gap. Returns
        ``(-1, None)`` when no consistent snapshot exists.
        """
        meta = self._memory_meta()
        if meta is None or not SharedMemory.exists(self._shm_name):
            return -1, None
        shm = self._shm or SharedMemory(self._shm_name)
        self._shm = shm
        buf = shm.buf
        catalog: Dict[str, List] = {}
        for t in meta.tensors:
            catalog.setdefault(t.path, []).append(
                (t, self._shm_reader(buf, t))
            )

        def read_region(path: str, region) -> np.ndarray:
            blocks = catalog.get(path)
            if not blocks:
                raise KeyError(f"no snapshot blocks for {path}")
            region = tuple((int(s), int(e)) for s, e in region)
            out = np.empty(
                tuple(e - s for s, e in region), dtype=blocks[0][0].dtype
            )
            # Same straggling-staging-thread guard as load().
            with self._write_mutex:
                self._region_fill(out, region, blocks, exact_pairs=None)
            return out

        return meta.step, read_region

    def _load_from_storage(self, template) -> Tuple[int, Any]:
        """Storage restore with a verified fallback chain.

        The tracker's step is only the *first* candidate: if it turns out
        missing, torn or checksum-corrupt, the next older step directory
        is tried, and so on — a damaged newest checkpoint costs one
        checkpoint interval of progress, never the whole run. Each
        rejected step is quarantined (see :mod:`ckpt_persist`) with its
        reason, and the chain is surfaced in ``last_restore_stats``
        (``step``/``fallback_from``/``fallback_reason``/``skipped``).

        Template/shape mismatches ("model definition changed") propagate
        instead of falling back: a healthy checkpoint that no longer fits
        the model is a user error, and quarantining it — or silently
        restoring an older one that happens to fit — would hide it.
        """
        tracker = ckpt_persist.read_tracker(self.storage, self.checkpoint_dir)
        all_steps = ckpt_persist.list_steps(self.storage, self.checkpoint_dir)
        if tracker is not None:
            candidates = [s for s in all_steps if s <= tracker]
        else:
            # No/unreadable tracker (lost with the master's disk): any
            # step dir that fully verifies beats a cold start.
            candidates = list(all_steps)
        skipped: List[Tuple[int, str]] = []
        for step in reversed(candidates):
            if ckpt_persist.is_quarantined(
                self.storage, self.checkpoint_dir, step
            ):
                skipped.append((step, "quarantined"))
                continue
            # Phase counters restart per attempt (and on the
            # memory->storage fallback): a failed attempt must not leak
            # its phase times into the winning step's attribution.
            self._reset_restore_stats()
            t_load0 = time.perf_counter()
            try:
                nbytes, n_shards, state = self._restore_step(template, step)
            except ckpt_persist.StepCorruptionError as e:
                ckpt_persist.quarantine_step(
                    self.storage, self.checkpoint_dir, step, e.reason
                )
                skipped.append((step, e.reason))
                continue
            self._cached_step = step
            self._finish_restore_stats("storage", nbytes, t_load0)
            s = self._restore_stats
            s["step"] = step
            s["skipped"] = list(skipped)
            if skipped:
                s["fallback_from"], s["fallback_reason"] = skipped[0]
            logger.info(
                "restored step %s from storage (%s shard files, %s)",
                step, n_shards, self._restore_stats,
            )
            if skipped:
                emit(
                    EventKind.CKPT_FALLBACK, to_step=step,
                    from_step=s["fallback_from"],
                    reason=s["fallback_reason"],
                )
            emit(
                EventKind.CKPT_RESTORE, source="storage", step=step,
                duration_s=round(time.perf_counter() - t_load0, 3),
            )
            emit(
                EventKind.CKPT_IO, op="read", step=step,
                bytes=int(nbytes), mbps=round(s["read_mbps"], 1),
                verify_s=round(s["verify_s"], 4),
            )
            return step, state
        if skipped:
            logger.error(
                "no restorable checkpoint in %s; every candidate was "
                "damaged: %s", self.checkpoint_dir, skipped,
            )
            self._restore_stats["skipped"] = list(skipped)
        return -1, template

    def _restore_step(self, template, step: int) -> Tuple[int, int, Any]:
        """Rebuild `template` from one persisted step, fully verified.

        One positional reader is opened per shard bin and shared by all of
        its block reads (replacing the open-per-block pattern); striped
        metas are stripe-verified in parallel up front, which localizes
        corruption and lets the block reads themselves skip re-hashing.

        Raises :class:`ckpt_persist.StepCorruptionError` when the step is
        structurally broken (no/undecodable/missing shard metas, missing
        or truncated bins) or any stripe/block fails its checksum."""
        metas = ckpt_persist.load_step_metas(
            self.storage, self.checkpoint_dir, step
        )
        if not metas:
            raise ckpt_persist.StepCorruptionError(
                step, "no readable shard metas"
            )
        expected = max(m.global_shard_num for m in metas.values())
        missing = sorted(set(range(expected)) - set(metas))
        if missing:
            raise ckpt_persist.StepCorruptionError(
                step, f"missing shard metas {missing} of {expected}"
            )
        catalog: Dict[str, List] = {}
        objects: Dict[str, Any] = {}
        nbytes = 0
        readers: List[Any] = []
        try:
            for gid in sorted(metas):
                meta = metas[gid]
                algo = getattr(meta, "crc_algo", "")
                # Routed reader: a step persisted incrementally resolves
                # stripes referencing earlier steps' bins transparently;
                # for a self-contained step this is a plain shard reader.
                reader = ckpt_persist.open_routed_reader(
                    self.storage, self.checkpoint_dir, step, gid, meta
                )
                if reader is None and meta.tensors:
                    raise ckpt_persist.StepCorruptionError(
                        step, f"shard {gid} bin missing"
                    )
                if reader is not None:
                    reader = _CountingReader(reader, self._restore_stats)
                    readers.append(reader)
                    t_v0 = time.perf_counter()
                    ckpt_persist.verify_stripes(reader, meta, step, gid)
                    if hasattr(self, "_restore_stats"):
                        self._restore_stats["verify_s"] += (
                            time.perf_counter() - t_v0
                        )
                for k, v in meta.objects.items():
                    objects.setdefault(k, v)
                for t in meta.tensors:
                    nbytes += t.nbytes
                    catalog.setdefault(t.path, []).append(
                        (t, self._storage_reader(step, gid, t, algo, reader))
                    )
            try:
                state = self._rebuild(template, catalog, objects)
            except KeyError as e:
                saved_zero = max(
                    (getattr(m, "zero_degree", 0) for m in metas.values()),
                    default=0,
                )
                if "cover" in str(e) and saved_zero != self.zero_degree:
                    # The persisted blocks don't tile the requested leaf and
                    # the ZeRO degrees disagree: optimizer slices saved under
                    # one data degree are being restored under another. This
                    # error is NOT StepCorruptionError on purpose — the
                    # fallback chain must not skip to an older step and load
                    # a wrong slice silently; it propagates to the caller.
                    raise ckpt_persist.ZeroDegreeMismatchError(
                        step, saved_zero, self.zero_degree, str(e)
                    ) from e
                if "cover" in str(e):
                    # Same ZeRO degree but the saved block catalog still
                    # can't tile the requested template: the checkpoint was
                    # written under a different mesh topology than the one
                    # restoring it, and the gap is structural, not data
                    # damage. Like the ZeRO case this propagates past the
                    # fallback chain — an older step saved under the same
                    # topology would have the same gap.
                    saved_axes = next(
                        (
                            getattr(m, "mesh_axes", None)
                            for m in metas.values()
                            if getattr(m, "mesh_axes", None)
                        ),
                        None,
                    )
                    raise ckpt_persist.TopologyMismatchError(
                        step, saved_axes, self.mesh_axes, str(e)
                    ) from e
                raise
        finally:
            for r in readers:
                try:
                    r.close()
                except OSError:
                    pass  # best-effort close; the read outcome already stands
        return nbytes, len(metas), state

    # ------------- restore attribution -------------
    @property
    def last_restore_stats(self) -> Dict[str, Any]:
        """Phase breakdown of the most recent ``load``: ``read_s``
        (wall time of the batched parallel block reads — direct preads
        into destination views plus staged reads; partial-overlap reads
        count under assemble) and the derived ``read_mbps``,
        ``verify_s`` (parallel stripe verification of striped shards),
        ``device_put_s`` (host->device transfers for sharded
        templates), ``assemble_s`` (region fill + batched memcpy =
        total - read - verify - device_put),
        ``total_s``, ``source``, ``bytes``; plus the verified-restore
        chain: ``step`` (the step actually restored), ``skipped``
        (list of (step, reason) pairs rejected on the way down) and,
        when a fallback happened, ``fallback_from``/``fallback_reason``
        naming the newest candidate and why it was rejected."""
        return dict(getattr(self, "_restore_stats", {}))

    def _reset_restore_stats(self):
        self._restore_stats = {
            "source": None, "read_s": 0.0, "verify_s": 0.0,
            "device_put_s": 0.0,
            "assemble_s": 0.0, "total_s": 0.0, "bytes": 0,
            "read_mbps": 0.0,
            "step": -1, "skipped": [],
            "fallback_from": None, "fallback_reason": None,
            # Broadcast-restore accounting: bytes actually pread from
            # storage (at the reader boundary, so verify+reads both count),
            # bytes moved host->device (once per unique region) and bytes
            # replicated device->device along the data axis.
            "storage_read_bytes": 0,
            "h2d_bytes": 0,
            "d2d_bytes": 0,
        }

    def _finish_restore_stats(self, source: str, nbytes: int, t0: float):
        s = self._restore_stats
        s["source"] = source
        s["bytes"] = int(nbytes)
        s["total_s"] = time.perf_counter() - t0
        s["assemble_s"] = max(
            0.0,
            s["total_s"] - s["read_s"] - s["verify_s"] - s["device_put_s"],
        )
        if s["read_s"] > 0:
            s["read_mbps"] = s["bytes"] / s["read_s"] / 1e6

    def _storage_reader(
        self, step: int, gid: int, t: TensorMeta, crc_algo: str = "",
        reader=None,
    ) -> Callable[[], np.ndarray]:
        """A block source over the shard's shared positional reader.

        The returned callable materializes the block (used by the
        partial-overlap reshard path); its ``read_into`` attribute preads
        the block straight into a preallocated destination view — the
        exact-match fast path, one copy total. Per-block checksums
        (legacy metas) are verified either way; striped metas carry
        ``crc=None`` here because stripe verification already covered
        every byte. Falls back to ``read_block`` when the storage could
        not produce a reader."""
        crc = getattr(t, "crc", None)

        def _corrupt(reason: str):
            return ckpt_persist.StepCorruptionError(
                step,
                f"{reason} in shard {gid} block {t.path!r} "
                f"(offset {t.offset}, {t.nbytes} bytes)",
            )

        def read() -> np.ndarray:
            if reader is None:
                # read_block raises StepCorruptionError itself on a
                # checksum mismatch; a missing/short block is promoted to
                # one here so the fallback chain treats both as "this
                # step is damaged".
                raw = ckpt_persist.read_block(
                    self.storage, self.checkpoint_dir, step, gid, t,
                    crc_algo,
                )
                if raw is None:
                    raise ckpt_persist.StepCorruptionError(
                        step,
                        f"block {t.path}{t.index} missing from shard {gid}",
                    )
                return np.frombuffer(raw, dtype=t.dtype).reshape(t.shape)
            raw = reader.read(t.offset, t.nbytes)
            if len(raw) != t.nbytes:
                raise _corrupt("missing/truncated block")
            if not checksum.verify_block(raw, crc, crc_algo):
                raise _corrupt("checksum mismatch")
            return np.frombuffer(raw, dtype=t.dtype).reshape(t.shape)

        if reader is not None:
            def read_into(dst: np.ndarray) -> None:
                got = reader.read_into(t.offset, dst)
                if got != t.nbytes:
                    raise _corrupt("missing/truncated block")
                if not checksum.verify_block(dst, crc, crc_algo):
                    raise _corrupt("checksum mismatch")

            read.read_into = read_into
        return read

    # ------------- rebuild -------------
    def _rebuild(self, template, catalog: Dict[str, List], objects: Dict):
        """Reconstruct the template pytree from available blocks.

        Unsharded template leaves get host numpy arrays (the caller's first
        jitted step commits them); GSPMD template leaves are assembled
        per-device from whatever block partitioning the checkpoint holds and
        wrapped via ``jax.make_array_from_single_device_arrays`` — the
        reshard-on-restore path for world-size/mesh changes.
        """
        import jax

        leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
        out = []
        exact_pairs = []  # (dst, reader) resolved via batched parallel copy
        for kp, leaf in leaves:
            path = jax.tree_util.keystr(kp)
            if path in catalog:
                out.append(
                    self._rebuild_leaf(leaf, catalog[path], exact_pairs)
                )
            elif path in objects:
                out.append(objects[path])
            else:
                raise KeyError(
                    f"checkpoint is missing leaf {path}; model definition "
                    "changed since the snapshot"
                )
        # Batched block reads run in a thread pool: time the phase at
        # its wall clock here (per-reader timers would race and sum
        # overlapping durations past total_s). Sources with a
        # ``read_into`` capability (storage restores) pread straight into
        # the preallocated destination views — no intermediate bytes, no
        # separate memcpy pass; the rest (shm restores) keep the
        # read-then-batched-copy path.
        direct = [p for p in exact_pairs
                  if getattr(p[1], "read_into", None) is not None]
        staged = [p for p in exact_pairs
                  if getattr(p[1], "read_into", None) is None]
        t_read0 = time.perf_counter()
        if direct:
            fastcopy.parallel_map(lambda p: p[1].read_into(p[0]), direct)
        srcs = fastcopy.parallel_map(
            lambda pair: fastcopy.as_bytes_view(pair[1]()), staged
        )
        if hasattr(self, "_restore_stats"):
            self._restore_stats["read_s"] += (
                time.perf_counter() - t_read0
            )
        fastcopy.copy_many(
            [(dst, src) for (dst, _), src in zip(staged, srcs)]
        )
        return jax.tree_util.tree_unflatten(treedef, out)

    def _rebuild_leaf(self, leaf, blocks: List, exact_pairs: List):
        """blocks: list of (TensorMeta, reader). Returns the restored leaf:
        numpy for unsharded templates, a sharded jax.Array for GSPMD ones."""
        import jax

        # The checkpoint's global shape must match the template exactly —
        # a changed model dimension must fail loudly, not load cropped or
        # zero-padded weights.
        t0 = blocks[0][0]
        saved_shape = tuple(
            t0.global_shape if t0.global_shape is not None else t0.shape
        )
        want_shape = tuple(int(d) for d in np.shape(leaf))
        if saved_shape != want_shape:
            raise KeyError(
                f"checkpoint leaf {t0.path} has global shape {saved_shape} "
                f"but the template wants {want_shape}; model definition "
                "changed since the snapshot"
            )
        # Per-leaf read memo: partial-overlap assembly touches a saved
        # block once per overlapping target region; cache the bytes so a
        # reshard reads each block once, not once per region.
        blocks = [(t, _memo_reader(r)) for t, r in blocks]
        sharded_template = (
            isinstance(leaf, jax.Array)
            and getattr(leaf, "sharding", None) is not None
            and len(leaf.sharding.device_set) > 1
        )
        if not sharded_template:
            shape = tuple(int(d) for d in np.shape(leaf))
            arr = np.empty(shape, dtype=blocks[0][0].dtype)
            # raises on gaps; exact matches land via the batched copy
            self._region_fill(
                arr, tuple((0, d) for d in shape), blocks, exact_pairs
            )
            return arr
        # GSPMD leaf: assemble each unique addressable block of the target
        # sharding, then broadcast-restore: the host bytes go to ONE device
        # per unique region (H2D), and every further device holding the
        # same region hydrates device-to-device from that first copy along
        # the data axis — replicas stop multiplying the host-link traffic.
        region_cache: Dict[Tuple, np.ndarray] = {}
        first_on_device: Dict[Tuple, Any] = {}
        stats = getattr(self, "_restore_stats", None)
        single_arrays = []
        for sh in leaf.addressable_shards:
            key = _index_key(sh.index, leaf.shape)
            host = region_cache.get(key)
            if host is None:
                shape = tuple(stop - start for start, stop in key)
                host = np.empty(shape, dtype=blocks[0][0].dtype)
                self._region_fill(host, key, blocks, exact_pairs=None)
                region_cache[key] = host
            t_put0 = time.perf_counter()
            src = first_on_device.get(key)
            if src is None:
                arr = jax.device_put(host, sh.device)
                first_on_device[key] = arr
                if stats is not None:
                    stats["h2d_bytes"] += int(host.nbytes)
            else:
                arr = jax.device_put(src, sh.device)
                if stats is not None:
                    stats["d2d_bytes"] += int(host.nbytes)
            single_arrays.append(arr)
            if stats is not None:
                stats["device_put_s"] += time.perf_counter() - t_put0
        return jax.make_array_from_single_device_arrays(
            tuple(int(d) for d in leaf.shape), leaf.sharding, single_arrays
        )

    @staticmethod
    def _region_fill(out: np.ndarray, region: Tuple[Tuple[int, int], ...],
                     blocks: List, exact_pairs: Optional[List]) -> bool:
        """Fill `out` (shaped as `region`) from the available blocks.

        Exact-index matches are deferred to the caller's batched parallel
        copy when `exact_pairs` is given; partial overlaps are assembled
        inline. Raises KeyError if the blocks do not cover the region.
        """
        region_size = int(np.prod([stop - start for start, stop in region]))
        if region_size == 0:
            return True
        for t, reader in blocks:
            t_index = t.index
            if t_index is None:
                t_index = tuple((0, d) for d in t.shape)
            if t_index == region:
                if exact_pairs is not None:
                    exact_pairs.append(
                        (fastcopy.as_bytes_view(out, writeback=True), reader)
                    )
                else:
                    np.copyto(out, reader())
                return True
        covered = 0
        for t, reader in blocks:
            t_index = t.index
            if t_index is None:
                t_index = tuple((0, d) for d in t.shape)
            inter = []
            for (rs, re), (bs, be) in zip(region, t_index):
                s, e = max(rs, bs), min(re, be)
                if s >= e:
                    inter = None
                    break
                inter.append((s, e))
            if inter is None:
                continue
            src = reader()
            src_sl = tuple(
                slice(s - bs, e - bs)
                for (s, e), (bs, _) in zip(inter, t_index)
            )
            dst_sl = tuple(
                slice(s - rs, e - rs)
                for (s, e), (rs, _) in zip(inter, region)
            )
            out[dst_sl] = src[src_sl]
            covered += int(np.prod([e - s for s, e in inter]))
        if covered < region_size:
            raise KeyError(
                f"checkpoint blocks cover {covered}/{region_size} elements "
                f"of region {region}; topology changed beyond what the "
                "saved shards can rebuild"
            )
        return True

    # ------------- misc -------------
    @property
    def cached_step(self) -> int:
        return self._cached_step

    def wait_persisted(self, step: int, timeout: float = 120.0) -> bool:
        """Block until a step >= `step` is committed in storage.

        `>=` because the async saver may chase a newer snapshot when the
        trainer outpaces it; the committed step is never older than asked.
        """
        from dlrover_tpu.common.backoff import poll_until

        def committed() -> bool:
            tracker = ckpt_persist.read_tracker(
                self.storage, self.checkpoint_dir
            )
            return tracker is not None and tracker >= step

        return poll_until(committed, timeout, initial=0.05, max_delay=1.0)

    def close(self):
        done = self.wait_staged(30.0)
        self._stage_pool.shutdown(wait=False)
        if self._staging is not None and not self._staging.done():
            # A wedged staging thread still owns the buffer — leave the shm
            # mapping open rather than yank it out from under the write.
            logger.warning(
                "checkpoint staging still in flight at close; leaving shm "
                "mapped (done=%s)", done,
            )
            return
        if self._shm is not None:
            self._shm.close()
