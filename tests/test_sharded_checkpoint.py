"""Sharded + donation-safe flash-checkpoint tests on the 8-device CPU mesh.

The contract: async saves must survive a train step that
donates its input state, and GSPMD-sharded states must stage only
addressable blocks, persist each byte once, and restore under a *different*
mesh (reshard-on-restore). Capability parity:
``dlrover/trainer/torch/flash_checkpoint/fsdp_engine.py:158-224`` and
``atorch/atorch/utils/fsdp_save_util.py``.
"""

import dataclasses
import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.accel import ParallelSpec, auto_accelerate
from dlrover_tpu.common import ckpt_persist
from dlrover_tpu.common.ckpt_meta import ckpt_shm_name
from dlrover_tpu.common.shared_memory import SharedMemory
from dlrover_tpu.common.storage import PosixDiskStorage
from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn
from dlrover_tpu.train.checkpoint import CheckpointEngine


def token_loss(module, params, batch):
    return loss_fn(module.apply({"params": params}, batch), batch)


def tiny_cfg(**kw):
    return dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32, **kw)


def accelerate(spec):
    cfg = tiny_cfg()
    model = GPT(cfg)
    opt = optax.adamw(1e-3)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
    )
    res = auto_accelerate(model, opt, tokens, token_loss, spec=spec)
    batch = jax.device_put(tokens, res.batch_sharding)
    return res, batch


def tree_allclose(a, b, **kw):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), **kw
        )


@pytest.fixture
def shm_cleanup(job_name):
    yield
    SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))


class TestDonationSafety:
    def test_async_save_survives_donating_step(self, job_name, tmp_path,
                                               shm_cleanup):
        """save_async(state); state = train_step(state) — the documented
        loop. The train step donates its input; the staged snapshot must
        still hold the *pre-step* values."""
        res, batch = accelerate(ParallelSpec(data=1))
        state = res.state
        state, _ = res.train_step(state, batch)  # warm/compile
        expect = jax.device_get(state)  # pre-donation values
        engine = CheckpointEngine(str(tmp_path / "ckpts"))
        try:
            assert engine.save_to_memory_async(1, state)
            # Donate the saved state's buffers immediately.
            state, _ = res.train_step(state, batch)
            jax.block_until_ready(state)
            assert engine.wait_staged(timeout=60.0), (
                "async staging failed after donation — snapshot must not "
                "reference donated buffers"
            )
            step, restored = engine.load(jax.device_get(state))
            assert step == 1
            tree_allclose(restored, expect)
        finally:
            engine.close()

    def test_repeated_overlapped_saves_land(self, job_name, tmp_path,
                                            shm_cleanup):
        """An async save issued every step while training runs ahead: each
        completed staging must hold a consistent (step-tagged) snapshot."""
        res, batch = accelerate(ParallelSpec(data=1))
        state = res.state
        engine = CheckpointEngine(str(tmp_path / "ckpts"))
        landed = 0
        try:
            for s in range(1, 6):
                if engine.save_to_memory_async(s, state):
                    landed += 1
                state, _ = res.train_step(state, batch)
            assert engine.wait_staged(timeout=60.0)
            assert landed >= 1
            assert engine._memory_meta().step >= 1
        finally:
            engine.close()


class TestShardedStaging:
    def test_stages_blocks_not_full_arrays(self, job_name, tmp_path,
                                           shm_cleanup):
        """An fsdp-sharded leaf stages 8 index-tagged blocks; a replicated
        leaf stages one full block."""
        res, batch = accelerate(ParallelSpec(fsdp=8))
        engine = CheckpointEngine(str(tmp_path / "ckpts"))
        try:
            assert engine.save_to_memory(1, res.state, block=True)
            meta = engine._memory_meta()
            emb_blocks = [
                t for t in meta.tensors
                if t.path == "['params']['wte']['embedding']"
            ]
            emb = res.state["params"]["wte"]["embedding"]
            assert len(emb_blocks) == 8
            for t in emb_blocks:
                assert t.global_shape == tuple(emb.shape)
                assert t.index is not None
                assert t.shape[1] == emb.shape[1] // 8
                assert t.persist
            # step counter is replicated -> one whole block
            step_blocks = [
                t for t in meta.tensors if t.path == "['step']"
            ]
            assert len(step_blocks) == 1
            assert step_blocks[0].index is None
        finally:
            engine.close()

    def test_sharded_memory_roundtrip(self, job_name, tmp_path, shm_cleanup):
        res, batch = accelerate(ParallelSpec(data=2, fsdp=4))
        state = res.state
        state, _ = res.train_step(state, batch)
        expect = jax.device_get(state)
        engine = CheckpointEngine(str(tmp_path / "ckpts"))
        try:
            assert engine.save_to_memory(1, state, block=True)
            # Fresh template with the same shardings (a restarted trainer).
            template = res.init_fn(jax.random.PRNGKey(9))
            step, restored = engine.load(template)
            assert step == 1
            # Restored leaves carry the template's shardings.
            emb = restored["params"]["wte"]["embedding"]
            assert emb.sharding == template["params"]["wte"]["embedding"].sharding
            tree_allclose(restored, expect)
        finally:
            engine.close()

    def test_disk_persists_each_byte_once(self, job_name, tmp_path,
                                          shm_cleanup):
        """Replicated leaves must not hit disk N times; the shard file holds
        exactly one copy of every logical element."""
        res, _ = accelerate(ParallelSpec(data=8))  # fully replicated
        engine = CheckpointEngine(str(tmp_path / "c"))
        try:
            assert engine.save_to_storage(1, res.state)
            metas = ckpt_persist.load_step_metas(
                PosixDiskStorage(), str(tmp_path / "c"), 1
            )
            total_logical = sum(
                int(np.prod(np.asarray(l).shape)) * np.asarray(l).dtype.itemsize
                for l in jax.tree_util.tree_leaves(jax.device_get(res.state))
            )
            total_disk = sum(
                t.nbytes for m in metas.values() for t in m.tensors
            )
            assert total_disk == total_logical
        finally:
            engine.close()


class TestMultiProcess:
    """True multi-process GSPMD: 4 single-device processes save a sharded
    state no process fully addresses; 2 processes restore it."""

    def _spawn(self, nproc, mode, steps, ckpt_dir, losses_out, job):
        import subprocess
        import sys

        from conftest import REPO, cpu_subprocess_env

        from dlrover_tpu.common.rpc import find_free_port

        coord = f"127.0.0.1:{find_free_port()}"
        worker = os.path.join(REPO, "tests", "workers",
                              "sharded_ckpt_worker.py")
        procs = [
            subprocess.Popen(
                [sys.executable, worker, "--coordinator", coord,
                 "--nproc", str(nproc), "--rank", str(r),
                 "--ckpt-dir", ckpt_dir, "--mode", mode,
                 "--steps", str(steps), "--losses-out", losses_out],
                env=cpu_subprocess_env({"DLROVER_TPU_JOB_NAME": job}),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            )
            for r in range(nproc)
        ]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out.decode())
        assert all(p.returncode == 0 for p in procs), "\n".join(outs)

    def test_4proc_save_2proc_resume(self, job_name, tmp_path):
        import json

        ckpt_dir = str(tmp_path / "ckpts")
        out_a = str(tmp_path / "save.json")
        out_b = str(tmp_path / "resume.json")
        self._spawn(4, "save", 3, ckpt_dir, out_a, job_name + "-a")
        metas = ckpt_persist.load_step_metas(
            PosixDiskStorage(), ckpt_dir, 3
        )
        assert len(metas) == 4  # one shard file per saving process
        self._spawn(2, "resume", 5, ckpt_dir, out_b, job_name + "-b")
        resumed = json.load(open(out_b))
        assert resumed["start"] == 3
        # Continued losses must match an uninterrupted single-process run
        # of the same batch/model (different mesh => looser fp tolerance).
        res, batch = accelerate(ParallelSpec(fsdp=8))
        state = res.state
        base = []
        for _ in range(5):
            state, m = res.train_step(state, batch)
            base.append(float(m["loss"]))
        np.testing.assert_allclose(
            resumed["losses"], base[3:], rtol=1e-4, atol=1e-4
        )


class TestReshardOnRestore:
    @pytest.mark.parametrize(
        "save_spec,load_spec",
        [
            (ParallelSpec(fsdp=8), ParallelSpec(fsdp=4, data=2)),
            (ParallelSpec(fsdp=8), ParallelSpec(data=8)),
            (ParallelSpec(data=8), ParallelSpec(fsdp=8)),
            (ParallelSpec(data=2, fsdp=2, tensor=2),
             ParallelSpec(fsdp=8)),
        ],
        ids=["fsdp8-to-fsdp4", "fsdp8-to-dp", "dp-to-fsdp8", "3d-to-fsdp8"],
    )
    def test_storage_reshard(self, save_spec, load_spec, job_name, tmp_path,
                             shm_cleanup):
        """Save under one mesh, restore under another, training continues
        with the same losses as an uninterrupted run."""
        ckpt_dir = str(tmp_path / "ckpts")
        # Uninterrupted baseline under the *load* spec.
        res_b, batch_b = accelerate(load_spec)
        state_b = res_b.state
        base_losses = []
        for _ in range(5):
            state_b, m = res_b.train_step(state_b, batch_b)
            base_losses.append(float(m["loss"]))

        # Train 3 steps under save_spec, persist, drop everything.
        res_a, batch_a = accelerate(save_spec)
        state_a = res_a.state
        for _ in range(3):
            state_a, _ = res_a.train_step(state_a, batch_a)
        engine = CheckpointEngine(ckpt_dir)
        assert engine.save_to_storage(3, state_a)
        engine.close()
        del state_a, res_a
        SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))

        # Restart under load_spec, restore, continue 2 steps.
        res_c, batch_c = accelerate(load_spec)
        engine2 = CheckpointEngine(ckpt_dir)
        try:
            template = res_c.state
            step, restored = engine2.load(template)
            assert step == 3
            cont_losses = []
            state = restored
            for _ in range(2):
                state, m = res_c.train_step(state, batch_c)
                cont_losses.append(float(m["loss"]))
            np.testing.assert_allclose(
                cont_losses, base_losses[3:], rtol=2e-5, atol=2e-5
            )
        finally:
            engine2.close()
