"""Trainer-side library: process bootstrap, flash checkpoint, elastic data."""

import os
import time as _time
from typing import Dict, Optional

from dlrover_tpu.common import env_utils
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import logger

# Process-entry timestamp: with the agent's DLROVER_TPU_SPAWN_TS this
# yields the spawn->entry phase (fork + python + imports) of the
# restart-latency breakdown.
_ENTRY_TS = _time.time()
_INIT_DONE_TS: Optional[float] = None


# The cache directory is part of the cache key, so it must not move
# between runs: no job name, pid or temp dir in it.
CHECKOUT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    A relaunched worker replays every jit compile unless the executable
    cache survives the process, so every incarnation of every worker
    must land in one directory. Where ``JAX_COMPILATION_CACHE_DIR`` is
    set the cache is placed from outside and no directory is set here;
    otherwise it is the fixed ``.jax_cache`` beside the package. The
    thresholds are zeroed so that even a short compile is cached.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        cache_dir = CHECKOUT_COMPILE_CACHE
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    logger.info("persistent compile cache at %s", cache_dir)
    return cache_dir


def init_training(coordinator_addr: Optional[str] = None,
                  num_processes: Optional[int] = None,
                  process_id: Optional[int] = None,
                  compile_cache: bool = True):
    """Initialize JAX distributed from the agent's env handoff.

    The elastic agent exports ``DLROVER_TPU_COORDINATOR_ADDR`` /
    ``NUM_PROCESSES`` / ``PROCESS_ID`` for every worker; this is the analog
    of torchrun's env contract feeding ``init_process_group`` (reference
    ``training.py:433``), lowered to ``jax.distributed.initialize``.
    Also enables the persistent compilation cache (restart-cheapness;
    ``enable_compile_cache``) unless ``compile_cache=False``.

    No-op for single-process jobs so the same script runs standalone.
    """
    global _INIT_DONE_TS
    import jax

    if compile_cache:
        enable_compile_cache()

    coordinator = coordinator_addr or os.getenv(NodeEnv.COORDINATOR_ADDR, "")
    n = num_processes or int(os.getenv(NodeEnv.NUM_PROCESSES, "1"))
    pid = process_id if process_id is not None else int(
        os.getenv(NodeEnv.PROCESS_ID, "0")
    )
    if n <= 1 or not coordinator:
        logger.info("single-process run; skipping jax.distributed.initialize")
        _INIT_DONE_TS = _time.time()
        return
    logger.info(
        "jax.distributed.initialize(coordinator=%s, num_processes=%s, "
        "process_id=%s)", coordinator, n, pid,
    )
    jax.distributed.initialize(
        coordinator_address=coordinator, num_processes=n, process_id=pid
    )
    _INIT_DONE_TS = _time.time()


def bootstrap_timings() -> Dict[str, float]:
    """Restart-latency phases the bootstrap can see (seconds):
    ``spawn_s`` (agent fork -> process entry: exec + imports; needs the
    agent's ``DLROVER_TPU_SPAWN_TS``) and ``init_s`` (``init_training``
    wall: compile-cache setup + jax.distributed). Callers add their own
    restore / first-step phases."""
    out: Dict[str, float] = {}
    spawn_ts = env_utils.SPAWN_TS.get()
    if spawn_ts:
        out["spawn_s"] = round(_ENTRY_TS - spawn_ts, 3)
    if _INIT_DONE_TS is not None:
        out["init_s"] = round(_INIT_DONE_TS - _ENTRY_TS, 3)
    return out


def global_rank() -> int:
    return int(os.getenv(NodeEnv.PROCESS_ID, "0"))


def world_size() -> int:
    return int(os.getenv(NodeEnv.NUM_PROCESSES, "1"))


def local_rank() -> int:
    return int(os.getenv(NodeEnv.LOCAL_RANK, "0"))


def restart_count() -> int:
    return int(os.getenv(NodeEnv.RESTART_COUNT, "0"))


def report_training_metrics(step: int, **extra):
    """Append a metrics record for the agent's TrainingMonitor to forward
    (parity: the reference's per-step metrics file the torch training
    monitor tails, ``monitor/training.py:79``). A no-op unless the agent
    exported ``ConfigPath.ENV_RUNTIME_METRICS``."""
    import json
    import time as _time

    from dlrover_tpu.common.constants import ConfigPath

    path = os.getenv(ConfigPath.ENV_RUNTIME_METRICS, "")
    if not path:
        return
    rec = {"step": int(step), "timestamp": _time.time(), **extra}
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        # Rotate: the monitor tails by offset and resets on shrink, so a
        # multi-million-step job must not grow the file without bound.
        try:
            if os.path.getsize(path) > 16 * 1024 * 1024:
                with open(path, "w") as f:
                    f.write(json.dumps(rec) + "\n")
                return
        except OSError:
            pass
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError as e:
        logger.warning("failed to write training metrics: %s", e)


from dlrover_tpu.train.elastic_trainer import ElasticTrainer  # noqa: E402,F401
