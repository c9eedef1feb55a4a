"""Test harness: force an 8-device virtual CPU platform before jax imports.

Mirrors the reference's strategy of testing multi-node logic without
multi-node hardware (SURVEY.md §4): collectives and shardings run on a
virtual 8-device CPU mesh; control-plane tests use an in-process master.
"""

import atexit
import os
import shutil
import tempfile

# Naming the CPU platform is also what puts the Pallas kernels in
# interpret mode (dlrover_tpu/ops/interpret.py).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tier-1 starts hundreds of workers. Placed from outside, their compile
# cache stays out of the checkout's .jax_cache (a large tree breaks the
# driver's copy of the repo).
_JAX_CACHE = tempfile.mkdtemp(prefix="dlrover_tpu_test_jax_cache_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _JAX_CACHE
atexit.register(shutil.rmtree, _JAX_CACHE, ignore_errors=True)

import contextlib
import resource
import uuid

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cpu_subprocess_env(extra=None):
    """Environment for spawning CPU-JAX subprocesses in tests: the CPU
    platform, the repo on PYTHONPATH, and no inherited master address."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("DLROVER_TPU_MASTER_ADDR", None)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO, env.get("PYTHONPATH", "")) if p
    )
    if extra:
        env.update(extra)
    return env


@pytest.fixture
def job_name(monkeypatch):
    """A unique job namespace so socket/shm names never collide."""
    name = f"test-{uuid.uuid4().hex[:8]}"
    monkeypatch.setenv("DLROVER_TPU_JOB_NAME", name)
    return name


@pytest.fixture
def file_size_limit():
    """``with file_size_limit(n):`` runs under a soft RLIMIT_FSIZE of `n`
    bytes, as a container with a file-size limit would (Python ignores
    SIGXFSZ, so a write past it fails with EFBIG)."""
    @contextlib.contextmanager
    def limited(nbytes):
        soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
        resource.setrlimit(resource.RLIMIT_FSIZE, (nbytes, hard))
        try:
            yield
        finally:
            resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))

    return limited
