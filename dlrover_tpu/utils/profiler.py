"""Training profiler — per-step timing, model cost analysis, MFU, and
XLA trace capture.

Capability parity with the reference's AProfiler
(``atorch/atorch/utils/prof.py:39-464``: per-module forward hooks
collecting flops/macs/duration, timeline export, GPU-utilization
estimate). The torch version hooks every ``nn.Module`` because eager
execution is observable; under jit there is nothing to hook — XLA fuses
the graph — so the TPU-first design measures at the three boundaries
that exist:

- **step timing** (host wall-clock per step, categorized phases:
  ``with prof.phase("data")``),
- **model cost** via ``jax.jit(...).lower().cost_analysis()`` — the
  *compiler's* flops/bytes for the exact compiled computation (more
  truthful than per-module analytical counts),
- **device timeline** via ``jax.profiler`` trace capture on a step
  schedule (the TensorBoard-viewable analog of AProfiler's timeline).

``utilization()`` reports MFU against the device's peak flops —
AProfiler's ``compute_gpu_utilization`` analog.
"""

import contextlib
import os
import time
from collections import defaultdict, deque
from typing import Any, Dict, List, Optional

from dlrover_tpu.common import env_utils
from dlrover_tpu.common.log import logger

# Peak dense fp/bf16 FLOPs by TPU generation substring (public specs).
_PEAK_FLOPS = (
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v6", 918e12),
)


def device_peak_flops(device=None) -> float:
    """Peak FLOP/s of ``device``. The CPU backend has none (0.0, so MFU
    reads -1); a TPU whose kind is neither in the table nor overridden
    is an error, not a silent MFU of -1."""
    import jax

    device = device or jax.devices()[0]
    kind = device.device_kind.lower()
    for key, peak in _PEAK_FLOPS:
        if key in kind:
            return peak
    peak = env_utils.PEAK_FLOPS.get()
    if not peak and device.platform == "tpu":
        raise ValueError(
            f"no peak FLOP/s known for TPU device kind "
            f"{device.device_kind!r}: add it to _PEAK_FLOPS or set "
            f"{env_utils.PEAK_FLOPS.name}"
        )
    return peak


class StepStats:
    """Bounded step-time accumulator.

    Samples live in a ring (``window`` newest) so a long run neither
    grows without bound nor pays an ever-larger full sort per
    ``percentile`` call — the sort cost is capped by the window.
    ``count`` stays the *total* number of observations (the report's
    step counter); ``mean``/``percentile`` describe the window.
    """

    def __init__(self, window: int = 1024):
        self.times: deque = deque(maxlen=window)
        self._total = 0
        self._window_sum = 0.0

    def add(self, dt: float):
        if len(self.times) == self.times.maxlen:
            self._window_sum -= self.times[0]
        self.times.append(dt)
        self._window_sum += dt
        self._total += 1

    @property
    def count(self) -> int:
        return self._total

    @property
    def mean(self) -> float:
        return self._window_sum / len(self.times) if self.times else 0.0

    def percentile(self, p: float) -> float:
        if not self.times:
            return 0.0
        xs = sorted(self.times)
        idx = min(len(xs) - 1, int(p / 100 * len(xs)))
        return xs[idx]


class PhaseBreakdown:
    """Per-step wall-time split into the four phases a host thread can
    actually see under async dispatch, with NO extra device syncs.

    The trainer hands over three raw host segments per step:

    - ``input_s``  — blocking on the input pipeline (``next(it)``),
    - ``dispatch_s`` — from input done to the jitted step's dispatch
      returning (host-side work; an injected host straggle lands here),
    - ``fence_s`` — blocking on the lag-1 metric fence (device-bound
      wait: the previous step's compute plus any exposed collective),
    - ``readback_s`` — converting the fenced metrics to host floats.

    The fence wall conflates compute with exposed-communication wait, so
    the split uses a rolling *best-case* fence (the window minimum) as
    the pure-compute estimate: ``collective_s`` is the excess over that
    floor — a degraded link inflates it while steady compute does not —
    and ``compute_s`` is ``dispatch_s`` plus the floor. A heuristic, but
    one whose failure direction is safe: host-side straggle can never
    masquerade as link straggle.

    Stats ride the same bounded :class:`StepStats` rings as step times.
    """

    KEYS = ("input_s", "compute_s", "collective_s", "readback_s")

    def __init__(self, window: int = 256, fence_window: int = 16):
        self._fences: deque = deque(maxlen=fence_window)
        self.stats: Dict[str, StepStats] = {
            k: StepStats(window) for k in self.KEYS
        }
        self.last: Dict[str, float] = {}

    def split(self, input_s: float, dispatch_s: float, fence_s: float,
              readback_s: float = 0.0) -> Dict[str, float]:
        self._fences.append(fence_s)
        base = min(self._fences)
        collective = max(0.0, fence_s - base)
        phases = {
            "input_s": input_s,
            "compute_s": dispatch_s + (fence_s - collective),
            "collective_s": collective,
            "readback_s": readback_s,
        }
        for k, v in phases.items():
            self.stats[k].add(v)
        self.last = phases
        return phases

    def report(self) -> Dict[str, Dict[str, float]]:
        return {
            k: {
                "mean_s": round(st.mean, 6),
                "p99_s": round(st.percentile(99), 6),
            }
            for k, st in self.stats.items()
        }


class Profiler:
    """Step/phase timing + cost analysis + trace capture.

    Usage::

        prof = Profiler(trace_dir="/tmp/trace", trace_steps=(10, 13))
        for step in range(steps):
            with prof.step():
                with prof.phase("data"):
                    batch = next(loader)
                state, metrics = train_step(state, batch)
                prof.fence(metrics["loss"])   # honored iff sync=True
        print(prof.report())

    Step-time honesty under async dispatch: a jitted step returns to the
    host in microseconds while the device still computes, so the plain
    wall clock measures *dispatch*, not the step. ``sync=True`` makes
    ``step()`` block on the value registered via :meth:`fence` (or on
    all devices when no fence was registered) before recording the
    time — true device-inclusive step times, at the cost of a full
    sync per step (use it for profiling runs, not the production
    pipelined loop). The default ``sync=False`` keeps the context
    non-blocking and the report labels its numbers
    ``timing: "dispatch"`` so nobody mistakes them for device time.
    """

    def __init__(self, trace_dir: str = "",
                 trace_steps: Optional[tuple] = None,
                 sync: bool = False):
        self._step_stats = StepStats()
        self._phase_stats: Dict[str, StepStats] = defaultdict(StepStats)
        self._trace_dir = trace_dir
        self._trace_steps = trace_steps or ()
        self._tracing = False
        self._step_index = 0
        self._cost: Optional[Dict] = None
        self._sync = bool(sync)
        self._fence = None

    # ------------- timing -------------
    def fence(self, value):
        """Register this step's output (array or pytree) as the sync
        point; in ``sync=True`` mode ``step()`` blocks on it before
        recording the step time. Returns ``value`` unchanged."""
        self._fence = value
        return value

    def _sync_now(self):
        import jax

        if self._fence is not None:
            jax.block_until_ready(self._fence)
            return
        # No fence registered: best-effort barrier on everything in
        # flight (not every backend exposes one — then dispatch time is
        # what gets recorded, same as sync=False).
        for d in jax.devices():
            try:
                d.synchronize_all_activity()
            except Exception:
                return

    @contextlib.contextmanager
    def step(self):
        self._maybe_start_trace()
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            if self._sync:
                self._sync_now()
            self._fence = None
            self._step_stats.add(time.perf_counter() - t0)
            self._step_index += 1
            self._maybe_stop_trace()

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self._phase_stats[name].add(time.perf_counter() - t0)

    # ------------- XLA trace capture -------------
    def _maybe_start_trace(self):
        if (
            self._trace_dir
            and not self._tracing
            and self._trace_steps
            and self._step_index == self._trace_steps[0]
        ):
            import jax

            jax.profiler.start_trace(self._trace_dir)
            self._tracing = True
            logger.info("profiler: trace started at step %s -> %s",
                        self._step_index, self._trace_dir)

    def _maybe_stop_trace(self):
        if self._tracing and self._step_index >= self._trace_steps[1]:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False
            logger.info("profiler: trace stopped at step %s",
                        self._step_index)

    # ------------- model cost -------------
    def analyze(self, jitted_fn, *example_args) -> Dict[str, Any]:
        """Compiler-reported cost of the jitted computation
        (flops / bytes accessed / output bytes), AProfiler's
        flops-profile analog but from XLA itself."""
        lowered = jitted_fn.lower(*example_args)
        cost = lowered.compile().cost_analysis()
        self._cost = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        }
        return dict(self._cost)

    def utilization(self, flops_per_step: Optional[float] = None,
                    device=None) -> float:
        """MFU in [0,1]: (flops/step) / (peak * mean step time)."""
        flops = flops_per_step or (self._cost or {}).get("flops", 0.0)
        peak = device_peak_flops(device)
        mean = self._step_stats.mean
        if not (flops and peak and mean):
            return -1.0
        return flops / mean / peak

    # ------------- per-module attribution -------------
    def module_costs(
        self,
        module,
        rng,
        *example_args,
        depth: int = 2,
        top_k: int = 0,
    ) -> List[Dict[str, Any]]:
        """Per-module FLOPs/bytes census — AProfiler's module table
        (``atorch/atorch/utils/prof.py:39-464``) rebuilt for jit: torch
        hooks every module because eager is observable; here a flax
        *method interceptor* records each submodule call (path + input
        shapes) during one abstract trace, then every recorded module is
        independently lowered and the **compiler's own** cost analysis
        (flops / bytes accessed) is attributed to its path.

        Rows are sorted by flops; ``share`` is relative to the root
        module's total. XLA's cost analysis counts a while-loop body
        ONCE, so a module lifted by ``nn.scan`` reports *per-iteration*
        cost — pass an unrolled config (``scan_layers=False``) for exact
        whole-stack accounting.
        """
        import jax
        import flax.linen as nn

        records = []
        seen = set()

        def interceptor(next_fn, args, kwargs, context):
            path = context.module.path
            if (
                context.method_name == "__call__"
                and 0 < len(path) <= depth
                and path not in seen
            ):
                seen.add(path)
                avals = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
                    if hasattr(a, "shape") else a,
                    (args, kwargs),
                )
                records.append(
                    (path, context.module.clone(parent=None), avals)
                )
            return next_fn(*args, **kwargs)

        def trace():
            with nn.intercept_methods(interceptor):
                return module.init(rng, *example_args)

        jax.eval_shape(trace)

        def cost_of(mod, avals):
            a_args, a_kwargs = avals

            def f(variables, *xs):
                return mod.apply(variables, *xs, **a_kwargs)

            abstract_vars = jax.eval_shape(
                lambda *xs: mod.init(rng, *xs), *a_args
            )
            lowered = jax.jit(f).lower(abstract_vars, *a_args)
            cost = lowered.compile().cost_analysis()
            if isinstance(cost, list):
                cost = cost[0] if cost else {}
            return (
                float(cost.get("flops", 0.0)),
                float(cost.get("bytes accessed", 0.0)),
            )

        rows = []
        for path, mod, avals in records:
            try:
                flops, bytes_ = cost_of(mod, avals)
            except Exception as e:  # non-callable aux modules etc.
                logger.debug("module_costs: skip %s (%s)", path, e)
                continue
            rows.append({
                "path": "/".join(path),
                "type": type(mod).__name__,
                "flops": flops,
                "bytes_accessed": bytes_,
            })
        total = sum(
            r["flops"] for r in rows if "/" not in r["path"]
        ) or max((r["flops"] for r in rows), default=0.0)
        for r in rows:
            r["share"] = round(r["flops"] / total, 4) if total else 0.0
        rows.sort(key=lambda r: -r["flops"])
        self._module_rows = rows
        return rows[:top_k] if top_k else rows

    # ------------- report -------------
    def report(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "steps": self._step_stats.count,
            # Under async dispatch only a synced profiler measures the
            # device; label the numbers so dashboards can't lie.
            "timing": "synced" if self._sync else "dispatch",
            "step_time_mean_s": round(self._step_stats.mean, 6),
            "step_time_p50_s": round(self._step_stats.percentile(50), 6),
            "step_time_p99_s": round(self._step_stats.percentile(99), 6),
            "phases": {
                name: {
                    "mean_s": round(st.mean, 6),
                    "share": round(
                        st.mean / self._step_stats.mean, 4
                    ) if self._step_stats.mean else 0.0,
                }
                for name, st in self._phase_stats.items()
            },
        }
        if self._cost:
            out["cost_analysis"] = dict(self._cost)
            mfu = self.utilization()
            if mfu >= 0:
                out["mfu"] = round(mfu, 4)
        return out


# Reference-compatible alias (AProfiler is the name users know).
AProfiler = Profiler
