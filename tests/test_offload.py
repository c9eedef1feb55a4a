"""Host-offload training tests.

Parity: the reference's CPU-offloaded Adam
(``atorch/atorch/optimizers/adam_offload.py``) and selective activation
offload (``selective_offloading_checkpoint.py``). Here the mechanisms
are XLA memory spaces: the optimizer state lives in ``pinned_host`` and
updates run in a ``compute_on("device_host")`` region; activations
offload via the ``offload`` remat policy. Numerics must match the
on-device baseline exactly — offload moves bytes, not math.

The HBM saving itself is only observable on a real accelerator (the CPU
backend's "host" and "device" memories are the same RAM); the TPU bench
carries that measurement.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.accel import ParallelSpec, auto_accelerate
from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn
from dlrover_tpu.optim.offload import (
    host_memory_kind_supported,
    offload,
    offload_shardings,
    offload_train_supported,
)

pytestmark = pytest.mark.skipif(
    not host_memory_kind_supported(),
    reason="backend has no pinned_host memory space",
)

# The CPU backend exposes the memory space but cannot execute jitted
# steps over host-resident state (it hoists producers onto host
# placements its runtime lacks); the full training path is validated on
# TPU (verified live + the bench's offload config). These CPU tests
# cover the plumbing: sharding construction, placement, composition.
_train_ok = offload_train_supported()
needs_train = pytest.mark.skipif(
    not _train_ok,
    reason="backend cannot execute host-resident-state train steps "
           "(TPU covers this)",
)


def token_loss(module, params, batch):
    return loss_fn(module.apply({"params": params}, batch), batch)


def run(spec, offload_opt, steps=3):
    cfg = dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32)
    model = GPT(cfg)
    opt = optax.adamw(1e-3)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
    )
    res = auto_accelerate(
        model, opt, tokens, token_loss, spec=spec,
        offload_optimizer=offload_opt,
    )
    state = res.state
    batch = jax.device_put(tokens, res.batch_sharding)
    losses = []
    for _ in range(steps):
        state, m = res.train_step(state, batch)
        losses.append(float(m["loss"]))
    res.state = state
    return losses, res


class TestOffloadedOptimizer:
    @needs_train
    def test_matches_on_device_numerics(self):
        base, _ = run(ParallelSpec(), offload_opt=False)
        off, _ = run(ParallelSpec(), offload_opt=True)
        np.testing.assert_allclose(off, base, rtol=2e-5, atol=2e-5)

    def test_state_lives_in_host_memory(self):
        _, res = run(ParallelSpec(), offload_opt=True, steps=0)
        mu = res.state["opt"][0].mu["wte"]["embedding"]
        assert mu.sharding.memory_kind == "pinned_host"
        # params stay on device
        p = res.state["params"]["wte"]["embedding"]
        assert p.sharding.memory_kind != "pinned_host"

    def test_small_leaves_stay_on_device(self):
        _, res = run(ParallelSpec(), offload_opt=True, steps=0)
        count = res.state["opt"][0].count
        assert count.sharding.memory_kind != "pinned_host"
        # bias moments are tiny: not worth a placement annotation
        mu_b = res.state["opt"][0].mu["ln_f"]["bias"]
        assert mu_b.sharding.memory_kind != "pinned_host"

    @needs_train
    def test_composes_with_fsdp(self):
        base, _ = run(ParallelSpec(), offload_opt=False)
        off, res = run(ParallelSpec(fsdp=8), offload_opt=True)
        np.testing.assert_allclose(off, base, rtol=2e-5, atol=2e-5)
        mu = res.state["opt"][0].mu["wte"]["embedding"]
        assert mu.sharding.memory_kind == "pinned_host"
        # still sharded over fsdp while host-resident
        shard = mu.addressable_shards[0]
        assert shard.data.shape[1] == mu.shape[1] // 8

    @needs_train
    def test_composes_with_adam8bit(self):
        """Offload stacks with the quantized optimizer: 2 bytes/param
        of moments AND zero HBM for them."""
        from dlrover_tpu.optim.low_bit import adam8bit

        cfg = dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32)
        model = GPT(cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
        )
        res = auto_accelerate(
            model, adam8bit(1e-3), tokens, token_loss,
            spec=ParallelSpec(), offload_optimizer=True,
        )
        state = res.state
        batch = jax.device_put(tokens, res.batch_sharding)
        losses = []
        for _ in range(3):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]


class TestActivationOffload:
    def test_offload_remat_policy_trains_identically(self):
        cfg = dataclasses.replace(
            GPTConfig.tiny(), dtype=jnp.float32, remat=True,
            remat_policy="dots",
        )
        cfg_off = dataclasses.replace(cfg, remat_policy="offload")
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
        )

        def train(c):
            res = auto_accelerate(
                GPT(c), optax.adamw(1e-3), tokens, token_loss,
                spec=ParallelSpec(),
            )
            state = res.state
            batch = jax.device_put(tokens, res.batch_sharding)
            losses = []
            for _ in range(3):
                state, m = res.train_step(state, batch)
                losses.append(float(m["loss"]))
            return losses

        try:
            off = train(cfg_off)
        except Exception as e:
            if "annotate_device_placement" in str(e):
                pytest.skip(
                    "backend runtime cannot execute host-offloaded "
                    "residuals inside the remat+scan pattern (XLA-CPU "
                    "limitation; the TPU path is exercised by the "
                    "bench's offload config)"
                )
            raise
        np.testing.assert_allclose(off, train(cfg), rtol=2e-5, atol=2e-5)
