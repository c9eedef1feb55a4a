"""Event tracing — spans, instants and counters in Chrome-trace form.

Parity with the reference's tracing/diagnosis data collection (SURVEY §5:
the master records node events and training phase transitions for
offline diagnosis). Events are recorded in-process (thread-safe ring
buffer) in the Chrome trace event shape (``chrome://tracing`` /
Perfetto-viewable), on the wall clock (``time.time()``, µs), which is
what lines them up across processes and with ``goodput.json``.

Usage::

    from dlrover_tpu.utils.tracing import get_tracer
    tracer = get_tracer()
    with tracer.span("ckpt.fetch", step=3) as s:
        ...
    s.duration_s                       # the block's time; keep no stamps
    tracer.count("ckpt.skipped", reason="staging_in_flight")
    tracer.instant("worker-crash", rank=2)

Two sinks, one call. Every event goes to the ring and, where
``DLROVER_TPU_TRACE_FILE`` is set, to a file (below). A span opened in a
process that has JAX loaded also enters ``jax.profiler.TraceAnnotation``
under the same name, so while a profiler session runs the span sits in
the ``.xplane.pb`` on the device trace's clock, on its thread's line of
``/host:CPU``. This module never imports JAX itself: the launcher, the
agent and the master stay off it.

Files. The launcher/agent process exports its whole ring to the
variable's path as one Chrome-trace JSON document (``export()``: at
restarts and at exit). A worker — a process the agent started, which has
the rank variables of ``NodeEnv`` — appends to a file of its own,
``<path minus .json>.worker<local_rank>.<restart_count>.jsonl``, one
event a line, so no process overwrites another's. A thread's lines go out
in one unbuffered write when its outermost span closes (an event outside
any span at once): one system call a step, not one a record — on the
step path a write is what a record costs most, and it hands the
interpreter lock to whichever thread waits for it. A killed worker's
file therefore holds every whole line up to the kill, less the closed
children of spans that were still open.
"""

import atexit
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Deque, Dict, Optional

from dlrover_tpu.common import env_utils

#: Every span and counter of the program: name -> (layer, thread, what
#: the interval covers). The names are a contract with what reads them
#: (``benchmark/program_spans.py``, docs/observability.md); a test holds
#: this table to the call sites. None starts with ``bench.`` or ``Pjit``
#: or contains ``Execute``/``TransferTo``/``TransferFrom``: those name
#: the benchmark's own and the runtime's host events in a device trace.
SPANS: Dict[str, tuple] = {
    "trainer.step": ("trainer", "loop",
                     "one iteration of Trainer.fit; parent of the loop's "
                     "other spans"),
    "trainer.input": ("trainer", "loop", "next(batches)"),
    "input.host_next": ("trainer", "loop",
                        "DevicePrefetchIterator._fill: next() of the host "
                        "iterator"),
    "input.device_put": ("trainer", "loop",
                         "DevicePrefetchIterator._fill: device_put of the "
                         "host batch"),
    "trainer.dispatch": ("trainer", "loop",
                         "the chaos site, the sync loop's device_put and "
                         "train_step's (asynchronous) dispatch"),
    "trainer.save": ("trainer", "loop",
                     "checkpointer.save_checkpoint, MEMORY or DISK"),
    "ckpt.snapshot": ("checkpoint", "loop",
                      "engine._snapshot: the walk over leaves and shards"),
    "ckpt.own_copies": ("checkpoint", "loop",
                        "engine._own_copies: device_put of the state into "
                        "pinned_host"),
    "ckpt.skipped": ("checkpoint", "loop, staging",
                     "counter, by reason (staging_in_flight, governor, lock, "
                     "superseded): offered snapshots not taken or not landed"),
    "trainer.report": ("trainer", "loop",
                       "report_global_step RPC and report_training_metrics"),
    "trainer.fence": ("trainer", "loop",
                      "the lag-1 wait for the device (sync loop: "
                      "block_until_ready of this step)"),
    "trainer.readback": ("trainer", "loop",
                         "the fenced metrics read back as host floats"),
    "trainer.callbacks": ("trainer", "loop", "the on_step_end callbacks"),
    "ckpt.stage": ("checkpoint", "staging",
                   "engine._stage_async whole; parent of the staging spans"),
    "ckpt.fetch": ("checkpoint", "staging (loop in a sync save)",
                   "engine._fetch: the state from pinned_host (or the "
                   "device) into host arrays"),
    "ckpt.lock_wait": ("checkpoint", "staging (loop in a sync save)",
                       "the engine's write mutex and the shard lock shared "
                       "with the agent's saver"),
    "ckpt.shm_copy": ("checkpoint", "staging (loop in a sync save)",
                      "layout, segment (re)creation and fastcopy into "
                      "shared memory"),
    "ckpt.shm_flush": ("checkpoint", "staging (loop in a sync save)",
                       "the segment's flush"),
    "ckpt.publish": ("checkpoint", "staging (loop in a sync save)",
                     "the shard's meta record published; ends with the "
                     "snapshot restorable (cached_step)"),
    "ckpt-crash-flush": ("agent", "agent",
                         "the dead worker's snapshot written to disk before "
                         "the restart"),
    "rendezvous": ("agent", "agent", "one rendezvous round with the master"),
    "attn.pairs": ("kernels", "whichever traces the step",
                   "counter, by kind and seq, raised where a flash-attention "
                   "call is built (once a trace, not once a step): "
                   "kind=allowed the query-key pairs its mask allows, "
                   "kind=computed the pairs of the live sub-tiles of the "
                   "blocks its grid runs (what the kernel bodies compute)"),
    "attn.residuals": ("kernels", "whichever traces the step",
                       "counter, by kind, raised where a model builds the "
                       "flash-attention call of a remat'ed block (once a "
                       "trace, not once a step), by the bytes the forward "
                       "kernel writes for the backward ones (the output and "
                       "a float32 a row of log-sum-exp): kind=saved the "
                       "block's policy keeps them (dots, dots_lite) and the "
                       "forward kernel runs once a layer, kind=recomputed "
                       "its backward pass runs the forward kernel again "
                       "(nothing, offload)"),
    "accel.replicated_params": ("accel", "whichever builds the step",
                                "counter, by kind, raised once where a "
                                "strategy's shardings are built under fsdp "
                                "> 1: kind=leaves the parameter leaves no "
                                "mesh axis shards (the vectors a layer FSDP "
                                "keeps whole, scalars), kind=bytes their "
                                "bytes"),
    "attn.summaries": ("model", "device (jax.named_scope)",
                       "eva mixer: one learned summary of k and of v a "
                       "chunk of positions"),
    "attn.mix": ("model", "device (jax.named_scope)",
                 "eva mixer: the summaries laid before the keys and the "
                 "attention call over both"),
    "moe.route": ("model", "device (jax.named_scope)",
                  "held-experts layer: sigmoid scores over all routed "
                  "experts, the top-k choice and its weights"),
    "moe.dispatch": ("model", "device (jax.named_scope)",
                     "held-experts layer: the held pairs sorted by expert, "
                     "each expert's on whole tiles of the pair buffer"),
    "moe.experts": ("model", "device (jax.named_scope)",
                    "held-experts layer: the gather of the buffer's rows "
                    "and the three grouped matmuls of the SwiGLU over all "
                    "of them"),
    "moe.combine": ("model", "device (jax.named_scope)",
                    "held-experts layer: the weighted scatter-add of the "
                    "buffer's rows onto their tokens"),
    "moe.shared": ("model", "device (jax.named_scope)",
                   "held-experts layer: the shared expert, a SwiGLU over "
                   "every token"),
    "moe.pairs": ("trainer", "loop",
                  "counter, by kind, raised each step at the readback by "
                  "what the step before counted, a layer (the mean over the "
                  "expert layers): kind=held the token-expert pairs routed "
                  "to the experts this chip holds, kind=buffer the rows of "
                  "the pair buffer, kind=overflowed the held pairs that did "
                  "not fit (0 while the layer is dropless)"),
    "moe.load_max_over_mean": ("trainer", "loop",
                               "counter, raised each step at the readback "
                               "by the largest expert's pairs over the mean "
                               "of all routed experts' (the mean over the "
                               "expert layers); a reader takes its rise a "
                               "step"),
}

#: Lines a thread may hold back under a span that stays open.
_MAX_PENDING = 64

_annotation = None  # jax.profiler.TraceAnnotation, once JAX is in the process
# One encoder for every line (json.dumps with options builds one a call).
_encode = json.JSONEncoder(default=str, separators=(",", ":")).encode


def _trace_annotation():
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        _annotation = getattr(
            getattr(sys.modules["jax"], "profiler", None),
            "TraceAnnotation", None,
        )
    return _annotation


def worker_trace_path() -> Optional[str]:
    """The file this process appends its events to as they are made, or
    None: the variable is unset, or the process is no worker of an agent
    (a standalone script exports its ring like the agent does)."""
    path = env_utils.TRACE_FILE.get()
    if not path or not (
        env_utils.LOCAL_RANK.is_set() and env_utils.RESTART_COUNT.is_set()
    ):
        return None
    stem = path[:-len(".json")] if path.endswith(".json") else path
    return (
        f"{stem}.worker{env_utils.LOCAL_RANK.get()}"
        f".{env_utils.RESTART_COUNT.get()}.jsonl"
    )


class Span:
    """One interval, open for a ``with`` block. ``args`` may be added to
    inside the block (``bytes`` known only at its end); after it,
    ``duration_s`` is what the block took and ``start`` the
    ``perf_counter()`` it began at."""

    __slots__ = ("name", "args", "start", "duration_s", "_tracer", "_ts",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer, self.name, self.args = tracer, name, args
        self.start = self.duration_s = 0.0

    def __enter__(self) -> "Span":
        tracer, args = self._tracer, self.args
        stack = tracer._stack()
        args["id"] = next(tracer._ids)
        if stack:
            parent = stack[-1].args
            args["parent"] = parent["id"]
            if "step" not in args and "step" in parent:
                args["step"] = parent["step"]
        stack.append(self)
        annotate = _trace_annotation()
        self._annotation = annotate and annotate(self.name, **args)
        if self._annotation is not None:
            self._annotation.__enter__()
        self._ts = time.time()  # dtlint: disable=DT011 -- Chrome-trace wall stamp for profiling output, never journaled; replay-time traces carry replay-time clocks by design
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.duration_s = time.perf_counter() - self.start
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self._tracer._stack().pop()  # with-blocks of a thread nest
        self._tracer._record({
            "name": self.name, "ph": "X", "ts": self._ts * 1e6,
            "dur": self.duration_s * 1e6, "args": self.args,
        })
        return False


class Tracer:
    def __init__(self, capacity: int = 65536):
        self._events: Deque[Dict] = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._counters: Dict[str, Dict[str, float]] = {}
        # The worker's file, opened by the first event of a process: a
        # fork-server child inherits this object and is another process.
        self._sink = None
        self._sink_pid = 0

    def _stack(self) -> list:
        """This thread's open spans, outermost first."""
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = stack = []
            self._local.pending = []    # its lines not yet written
            return stack

    def _open_sink(self, pid: int):
        """Open (append, unbuffered: what is written is in the file, a
        kill holds nothing back) the file of worker ``pid``. Two threads
        racing here open it twice; both append whole lines, nothing is
        lost."""
        sink = None
        try:
            path = worker_trace_path()
            if path:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                sink = open(path, "ab", buffering=0)
        except OSError:
            sink = None
        self._sink, self._sink_pid = sink, pid
        return sink

    def _record(self, event: Dict):
        """Stamp the event with the process and thread that made it, keep
        it in the ring and write it to the worker's file."""
        # The pid is taken now, not at construction: a forked child that
        # inherits the tracer must not stamp its parent's.
        event["tid"] = threading.get_ident() % 1_000_000
        event["pid"] = pid = os.getpid()  # dtlint: disable=DT011 -- names the process in profiling output, never journaled; a replay's trace carries the replaying process by design
        with self._lock:
            self._events.append(event)
        sink = self._sink if pid == self._sink_pid else self._open_sink(pid)
        if sink is None:
            return
        stack, pending = self._stack(), self._local.pending
        pending.append(_encode(event).encode() + b"\n")
        if not stack or len(pending) >= _MAX_PENDING:
            try:
                sink.write(b"".join(pending))
            except (OSError, ValueError):
                self._sink = None  # tracing never fails the traced
            pending.clear()

    def span(self, name: str, **args) -> Span:
        """A complete ('X') event covering the with-block.

        The event's ``args`` gain ``id`` and, when another span is open
        on this thread, ``parent`` (its id) and — unless given — its
        ``step``, so every record under the loop's ``trainer.step`` says
        which step it belongs to. A staging span on another thread is
        tied to the loop span that dispatched it by ``step``."""
        return Span(self, name, args)

    def instant(self, name: str, **args):
        self._record({
            "name": name, "ph": "i", "s": "p",
            "ts": time.time() * 1e6, "args": args,  # dtlint: disable=DT011 -- Chrome-trace wall stamp for profiling output, never journaled; replay-time traces carry replay-time clocks by design
        })

    def count(self, name: str, n: float = 1, **labels):
        """Add ``n`` to the cumulative counter ``name`` and write a 'C'
        event with the new totals. Each set of labels is a series of the
        counter (``reason=lock``; ``value`` without labels), and the
        event carries all of them, as Chrome's counter tracks want."""
        if not n:
            return
        series = ",".join(
            f"{k}={v}" for k, v in sorted(labels.items())
        ) or "value"
        with self._lock:
            totals = self._counters.setdefault(name, {})
            totals[series] = totals.get(series, 0) + n
            args = dict(totals)
        self._record({
            "name": name, "ph": "C",
            "ts": time.time() * 1e6, "args": args,  # dtlint: disable=DT011 -- Chrome-trace wall stamp for profiling output, never journaled; replay-time traces carry replay-time clocks by design
        })

    @property
    def events(self):
        with self._lock:
            return list(self._events)

    def export(self, path: Optional[str] = None) -> Optional[str]:
        """Write the ring as Chrome trace JSON; default path from the env
        contract — for the launcher/agent process. A worker has written
        its events to its own file already and exports nothing onto the
        agent's path.

        Atomic (tmp + ``os.replace``, the port-file contract): exports
        fire mid-run and at exit, and a reader — or a crash between
        truncate and write — must never see a torn file."""
        if path is None and worker_trace_path() is None:
            path = env_utils.TRACE_FILE.get()
        if not path:
            return None
        with self._lock:
            events = list(self._events)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"traceEvents": events}, f, default=str)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return path


_tracer: Optional[Tracer] = None
_tracer_lock = threading.Lock()


def _export_at_exit():
    try:
        tracer = _tracer
        if tracer is not None:
            tracer.export()
    except Exception:  # dtlint: disable=DT001 -- atexit path: exits must never fail on tracing
        pass


def get_tracer() -> Tracer:
    global _tracer
    with _tracer_lock:
        if _tracer is None:
            _tracer = Tracer()
            if env_utils.TRACE_FILE.get():
                # The env contract asked for a file: make sure orderly
                # exits export even if no code path calls export().
                atexit.register(_export_at_exit)
        return _tracer


def read_events(path: str) -> list:
    """The events of a trace file of either shape: the agent's Chrome
    trace document or a worker's lines (a killed worker's last line may
    be cut short; it is dropped)."""
    with open(path) as f:
        if not path.endswith(".jsonl"):
            return list(json.load(f).get("traceEvents", ()))
        events = []
        for line in f:
            try:
                events.append(json.loads(line))
            except ValueError:
                continue
        return events
