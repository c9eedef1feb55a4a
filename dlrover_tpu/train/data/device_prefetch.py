"""Device-side batch prefetch — keep the accelerator pipeline full.

The host-side story (``ElasticDataLoader(prefetch=N)``) overlaps
*producing* a batch with training, but the batch still reaches the
device via a ``jax.device_put`` issued inside the step context, so the
H2D transfer of batch N+1 waits for the host to come back from step N.
``DevicePrefetchIterator`` closes that gap: it wraps any host batch
iterator and keeps ``depth`` batches already ``device_put`` to the
step's batch sharding, so when the training loop asks for the next
batch the transfer was dispatched one or more steps ago and the XLA
runtime has had a whole step of compute to hide it behind.

Semantics:

- ``device_put`` is async-dispatch: filling the buffer costs the host
  microseconds; the actual DMA overlaps the in-flight training step.
- ``StopIteration`` is clean: the wrapper drains its buffer after the
  source exhausts, so no prefetched batch is ever dropped at the tail.
- Elastic restart: ``swap(new_batches)`` atomically replaces the source
  iterator and discards still-buffered device batches (they belong to
  the old stream/world); the wrapper is then immediately usable again,
  even after exhaustion.
- Ack interplay: a loader that acks records as the consumer takes
  batches (``ElasticDataLoader`` + sharding client) sees its acks moved
  *earlier* by up to ``depth`` batches — after a crash up to ``depth``
  acked-but-untrained batches can be lost. Keep ``depth`` small (2 is
  enough to double-buffer) when exactly-once matters.
"""

import collections
from typing import Any, Iterable, Iterator, Optional

from dlrover_tpu.common.log import logger
from dlrover_tpu.utils.tracing import get_tracer


class DevicePrefetchIterator:
    """Wrap a host batch iterator; keep ``depth`` batches on device.

    ``sharding`` is applied to every leaf of each batch (the same
    contract as the training loop's previous inline ``device_put``);
    pass ``None`` to place on the default device.
    """

    def __init__(self, batches: Iterable, sharding: Any = None,
                 depth: int = 2):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._it: Iterator = iter(batches)
        self._sharding = sharding
        self.depth = depth
        self._buf: "collections.deque" = collections.deque()
        self._exhausted = False
        self._swaps = 0
        self._fill()

    # ------------- internals -------------
    def _put(self, host_batch):
        import jax

        if self._sharding is None:
            return jax.device_put(host_batch)
        return jax.device_put(host_batch, self._sharding)

    def _fill(self):
        """Dispatch transfers until ``depth`` batches are in flight."""
        import jax

        tracer = get_tracer()
        while not self._exhausted and len(self._buf) < self.depth:
            try:
                with tracer.span("input.host_next"):
                    host = next(self._it)
            except StopIteration:
                self._exhausted = True
                return
            nbytes = sum(
                getattr(leaf, "nbytes", 0)
                for leaf in jax.tree_util.tree_leaves(host)
            )
            with tracer.span("input.device_put", bytes=nbytes):
                self._buf.append(self._put(host))

    # ------------- iterator protocol -------------
    def __iter__(self) -> "DevicePrefetchIterator":
        return self

    def __next__(self):
        if not self._buf:
            # Source swapped after exhaustion, or depth batches were
            # never available: try to refill before giving up.
            self._fill()
            if not self._buf:
                raise StopIteration
        out = self._buf.popleft()
        # Refill BEFORE handing the batch back: the next H2D dispatch
        # rides ahead of the step the caller is about to launch.
        self._fill()
        return out

    # ------------- elastic restart -------------
    def swap(self, batches: Iterable,
             sharding: Optional[Any] = None) -> int:
        """Replace the source iterator (elastic restart / new epoch).

        Buffered device batches are discarded — they came from the old
        stream and may have the wrong shape for the new world size.
        Returns the number of discarded batches. ``sharding`` optionally
        re-targets the transfers (a restart may rebuild the mesh).
        """
        dropped = len(self._buf)
        self._buf.clear()
        self._it = iter(batches)
        if sharding is not None:
            self._sharding = sharding
        self._exhausted = False
        self._swaps += 1
        if dropped:
            logger.info(
                "device prefetch: source swapped, %s buffered batch(es) "
                "discarded", dropped,
            )
        self._fill()
        return dropped

    # ------------- introspection -------------
    @property
    def in_flight(self) -> int:
        """Batches currently buffered on device."""
        return len(self._buf)

    @property
    def exhausted(self) -> bool:
        """True when the source raised StopIteration AND the buffer is
        drained (a swap resets this)."""
        return self._exhausted and not self._buf

    @property
    def swaps(self) -> int:
        return self._swaps
