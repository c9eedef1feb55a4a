"""Replica-deduplicated checkpointing: writer election (journaled,
failover-durable), non-owner persist skip, broadcast + cross-topology
restore, content-hash incremental stripes, GC reference-closure pinning,
and the shared-stripe corruption drill.

The storage contracts are proven at the only layer that can't lie about
them — ``CountingStorage`` wraps the byte boundary, so "a skipped
replica writes nothing" and "restore reads each persisted byte once"
are byte-count assertions, not event inspection.
"""

import os
import pickle

import numpy as np
import pytest

from dlrover_tpu.common import ckpt_persist
from dlrover_tpu.common import messages as m
from dlrover_tpu.common.ckpt_meta import ckpt_shm_name
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.shared_memory import SharedMemory
from dlrover_tpu.common.storage import CountingStorage, PosixDiskStorage
from dlrover_tpu.master.kv_store import KVStoreService
from dlrover_tpu.train.checkpoint import CheckpointEngine

MB = 1 << 20


def big_state(nbytes=4 * MB, seed=0):
    """One big leaf so stripe arithmetic is exact and visible."""
    rng = np.random.default_rng(seed)
    return {"w": np.frombuffer(rng.bytes(nbytes), dtype=np.uint8).copy()}


def _close(engine, job):
    engine.close()
    SharedMemory.remove(ckpt_shm_name(job, 0, 0))


def _step_dirs(ckpt_dir):
    return sorted(
        d for d in os.listdir(ckpt_dir) if d.startswith("checkpoint-")
    )


# ---------------------------------------------------------------------------
# Writer election: setnx, journal replay, engine-side skip
# ---------------------------------------------------------------------------


class TestWriterElection:
    def test_setnx_first_claimant_wins(self):
        kv = KVStoreService()
        assert kv.setnx("k", b"3") == b"3"
        # Later claimants observe the winner, never overwrite it.
        assert kv.setnx("k", b"0") == b"3"
        assert kv.setnx("k", b"7") == b"3"
        assert kv.get("k") == b"3"
        assert kv.setnx("other", b"1") == b"1"

    def test_election_survives_master_failover(self, tmp_path):
        """The lease is a journaled mutation: a failed-over master must
        answer with the same owner it already promised (two writers in
        one epoch is the torn-checkpoint scenario the election exists to
        prevent)."""
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.master.master import JobMaster
        from tests.test_state_store import crash_master

        state_dir = str(tmp_path / "mstate")
        m1 = JobMaster(
            port=0, node_num=1, job_name="elect", state_dir=state_dir
        )
        m1.prepare()
        try:
            client = MasterClient(m1.addr, node_id=0)
            lease = client.elect_ckpt_writer("ck:shard0", 0, 3)
            assert lease.exists and lease.owner_rank == 3
            # A slower proposer of the same (group, epoch) sees rank 3.
            assert client.elect_ckpt_writer("ck:shard0", 0, 0).owner_rank == 3
            # A new epoch is a fresh election.
            assert client.elect_ckpt_writer("ck:shard0", 1, 1).owner_rank == 1
        finally:
            crash_master(m1)

        m2 = JobMaster(
            port=0, node_num=1, job_name="elect", state_dir=state_dir
        )
        m2.prepare()
        try:
            client2 = MasterClient(m2.addr, node_id=0)
            # Replayed from the WAL: the recovered master still answers
            # rank 3 for epoch 0, not this late proposer.
            assert (
                client2.elect_ckpt_writer("ck:shard0", 0, 1).owner_rank == 3
            )
            assert (
                client2.elect_ckpt_writer("ck:shard0", 1, 0).owner_rank == 1
            )
        finally:
            m2.stop()

    def test_non_owner_replica_writes_zero_bytes(self, job_name, tmp_path):
        """Two replicas of the same shard, one checkpoint dir, no
        master: replica 0 wins deterministically, replica 1's storage
        traffic for the save is exactly zero bytes."""
        ckpt_dir = str(tmp_path / "ckpts")
        state = big_state()
        st0 = CountingStorage(PosixDiskStorage())
        st1 = CountingStorage(PosixDiskStorage())
        jobs = [f"{job_name}-r0", f"{job_name}-r1"]
        e0 = CheckpointEngine(
            ckpt_dir, storage=st0, keep_latest=0, job=jobs[0],
            replica_rank=0, replica_count=2,
        )
        e1 = CheckpointEngine(
            ckpt_dir, storage=st1, keep_latest=0, job=jobs[1],
            replica_rank=1, replica_count=2,
        )
        try:
            assert e1.save_to_storage(5, state)  # non-owner goes first
            assert st1.write_bytes_total == 0
            assert e0.save_to_storage(5, state)
            assert st0.write_bytes_total >= 4 * MB
        finally:
            _close(e0, jobs[0])
            _close(e1, jobs[1])
        # What the single writer persisted restores for everyone.
        loader = CheckpointEngine(ckpt_dir, keep_latest=0, job=job_name)
        try:
            step, restored = loader.load(big_state(seed=1))
            assert step == 5
            np.testing.assert_array_equal(restored["w"], state["w"])
        finally:
            _close(loader, job_name)

    def test_persist_skip_event_keeps_gauge_honest(
        self, job_name, tmp_path
    ):
        from dlrover_tpu.observability import events as ev_mod

        seen = []
        sink = seen.append
        ev_mod.install_sink(sink)
        engine = CheckpointEngine(
            str(tmp_path / "ckpts"), keep_latest=0, job=job_name,
            replica_rank=1, replica_count=4,
        )
        try:
            assert engine.save_to_storage(1, big_state(nbytes=MB))
            ev_mod.flush_events()
            skips = [
                e for e in seen
                if e.kind == ev_mod.EventKind.CKPT_IO
                and e.args.get("op") == "persist-skip"
            ]
            assert len(skips) == 1
            assert skips[0].args["bytes"] == 0
            assert skips[0].args["replica"] == 1
            assert skips[0].args["owner"] == 0
        finally:
            ev_mod.uninstall_sink(sink)
            _close(engine, job_name)

    def test_engine_asks_master_and_honors_foreign_owner(
        self, job_name, tmp_path, monkeypatch
    ):
        """With a master configured the engine's election goes through
        the journaled RPC — a claim already on file (here: rank 1) beats
        the no-master replica-0 default, so replica 0 skips."""
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.master.master import JobMaster

        ckpt_dir = str(tmp_path / "ckpts")
        master = JobMaster(port=0, node_num=1, job_name="elx")
        master.prepare()
        monkeypatch.setenv(NodeEnv.MASTER_ADDR, master.addr)
        monkeypatch.setenv(NodeEnv.RESTART_COUNT, "0")
        MasterClient.reset()
        st = CountingStorage(PosixDiskStorage())
        engine = CheckpointEngine(
            ckpt_dir, storage=st, keep_latest=0, job=job_name,
            replica_rank=0, replica_count=2,
        )
        try:
            group = f"{ckpt_dir}:shard0"
            lease = MasterClient.singleton_instance().elect_ckpt_writer(
                group, 0, 1
            )
            assert lease.owner_rank == 1
            assert engine.save_to_storage(2, big_state(nbytes=MB))
            assert st.write_bytes_total == 0  # owner is replica 1, not us
        finally:
            _close(engine, job_name)
            MasterClient.reset()
            master.stop()


# ---------------------------------------------------------------------------
# Incremental stripes: content-hash refs, accounting, old pickles
# ---------------------------------------------------------------------------


class TestIncrementalStripes:
    def _engine(self, ckpt_dir, job, storage=None):
        return CheckpointEngine(
            ckpt_dir, storage=storage, keep_latest=0, job=job
        )

    def test_unchanged_stripes_ride_as_references(
        self, job_name, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("DLROVER_TPU_CKPT_STRIPE_MB", "1")
        ckpt_dir = str(tmp_path / "ckpts")
        state = big_state(8 * MB)
        st = CountingStorage(PosixDiskStorage())
        engine = self._engine(ckpt_dir, job_name, storage=st)
        try:
            assert engine.save_to_storage(1, state)
            full_write = st.write_bytes_total
            assert full_write >= 8 * MB
            st.reset_counts()
            state["w"][: 1024] ^= 0xFF  # dirty exactly stripe 0
            assert engine.save_to_storage(2, state)
            # One dirty stripe of eight: the rewrite persists a fraction
            # of the payload (stripe 0 + meta/commit bookkeeping).
            assert st.write_bytes_total < 0.15 * full_write
        finally:
            _close(engine, job_name)
        meta2 = ckpt_persist.load_step_metas(
            PosixDiskStorage(), ckpt_dir, 2
        )[0]
        refs = [s for s in meta2.stripes if s.ref_step >= 0]
        own = [s for s in meta2.stripes if s.ref_step < 0]
        assert len(meta2.stripes) == 8 and len(refs) == 7 and len(own) == 1
        assert own[0].offset == 0
        assert ckpt_persist.step_refs(meta2) == {1}
        # Routed restore resolves the referenced bytes transparently and
        # byte-exactly.
        loader = self._engine(ckpt_dir, f"{job_name}-l")
        try:
            step, restored = loader.load(big_state(8 * MB, seed=1))
            assert step == 2
            np.testing.assert_array_equal(restored["w"], state["w"])
        finally:
            _close(loader, f"{job_name}-l")

    def test_refs_flatten_to_original_owner(
        self, job_name, tmp_path, monkeypatch
    ):
        """Step 3's references point at the bins that physically hold
        the bytes — step 1 for clean stripes, step 2 for the stripe it
        rewrote — never at another referencing step (one-hop rule)."""
        monkeypatch.setenv("DLROVER_TPU_CKPT_STRIPE_MB", "1")
        ckpt_dir = str(tmp_path / "ckpts")
        state = big_state(4 * MB)
        engine = self._engine(ckpt_dir, job_name)
        try:
            assert engine.save_to_storage(1, state)
            state["w"][2 * MB + 5] ^= 0xFF  # dirty stripe 2
            assert engine.save_to_storage(2, state)
            assert engine.save_to_storage(3, state)  # unchanged
        finally:
            _close(engine, job_name)
        st = PosixDiskStorage()
        meta3 = ckpt_persist.load_step_metas(st, ckpt_dir, 3)[0]
        by_off = {s.offset: s.ref_step for s in meta3.stripes}
        assert by_off == {0: 1, MB: 1, 2 * MB: 2, 3 * MB: 1}
        assert ckpt_persist.step_refs(meta3) == {1, 2}

    def test_incremental_disable_env(self, job_name, tmp_path, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_CKPT_STRIPE_MB", "1")
        monkeypatch.setenv("DLROVER_TPU_CKPT_INCREMENTAL", "0")
        ckpt_dir = str(tmp_path / "ckpts")
        state = big_state(2 * MB)
        engine = self._engine(ckpt_dir, job_name)
        try:
            assert engine.save_to_storage(1, state)
            assert engine.save_to_storage(2, state)  # bit-identical state
        finally:
            _close(engine, job_name)
        meta2 = ckpt_persist.load_step_metas(
            PosixDiskStorage(), ckpt_dir, 2
        )[0]
        assert all(s.ref_step < 0 for s in meta2.stripes)
        assert ckpt_persist.step_refs(meta2) == set()

    def test_old_pickle_stripes_without_ref_step(
        self, job_name, tmp_path, monkeypatch
    ):
        """Satellite: metas pickled before ref_step existed (instance
        dict carries only offset/nbytes/crc) verify and restore under
        the routed reader — no flag day."""
        monkeypatch.setenv("DLROVER_TPU_CKPT_STRIPE_MB", "1")
        ckpt_dir = str(tmp_path / "ckpts")
        state = big_state(2 * MB)
        engine = self._engine(ckpt_dir, job_name)
        try:
            assert engine.save_to_storage(1, state)
        finally:
            _close(engine, job_name)
        meta_path = os.path.join(
            ckpt_persist.step_dir(ckpt_dir, 1), "shard_0.meta"
        )
        meta = pickle.loads(open(meta_path, "rb").read())
        for s in meta.stripes:
            s.__dict__.pop("ref_step", None)  # what an old pickle lacks
        open(meta_path, "wb").write(pickle.dumps(meta))

        st = PosixDiskStorage()
        assert ckpt_persist.step_refs(pickle.loads(
            open(meta_path, "rb").read()
        )) == set()
        ok, reason = ckpt_persist.verify_step(st, ckpt_dir, 1)
        assert ok, reason
        loader = self._engine(ckpt_dir, f"{job_name}-l")
        try:
            step, restored = loader.load(big_state(2 * MB, seed=1))
            assert step == 1
            np.testing.assert_array_equal(restored["w"], state["w"])
        finally:
            _close(loader, f"{job_name}-l")

    def test_no_dedup_checkpoint_restores_under_replica_engine(
        self, job_name, tmp_path
    ):
        """Satellite: a checkpoint written by a pre-dedup engine (no
        replica metadata, no mesh_axes on the meta) loads under a
        replica-aware engine unchanged."""
        ckpt_dir = str(tmp_path / "ckpts")
        state = big_state(MB)
        engine = CheckpointEngine(ckpt_dir, keep_latest=0, job=job_name)
        try:
            assert engine.save_to_storage(4, state)
        finally:
            _close(engine, job_name)
        # Strip the new meta fields the way an old pickle would lack them.
        meta_path = os.path.join(
            ckpt_persist.step_dir(ckpt_dir, 4), "shard_0.meta"
        )
        meta = pickle.loads(open(meta_path, "rb").read())
        meta.__dict__.pop("mesh_axes", None)
        open(meta_path, "wb").write(pickle.dumps(meta))

        loader = CheckpointEngine(
            ckpt_dir, keep_latest=0, job=f"{job_name}-l",
            replica_rank=2, replica_count=4, mesh_axes={"data": 4},
        )
        try:
            step, restored = loader.load(big_state(MB, seed=1))
            assert step == 4
            np.testing.assert_array_equal(restored["w"], state["w"])
        finally:
            _close(loader, f"{job_name}-l")


# ---------------------------------------------------------------------------
# Chaos drill: shared-stripe corruption + GC liveness
# ---------------------------------------------------------------------------


class TestSharedStripeChaos:
    def _three_steps(self, ckpt_dir, job):
        """Steps 1..3 with a reference chain: step 2 rewrites stripe 2,
        step 3 references stripe 2 from step 2 and the rest from step 1."""
        state = big_state(4 * MB)
        engine = CheckpointEngine(ckpt_dir, keep_latest=0, job=job)
        try:
            assert engine.save_to_storage(1, state)
            state["w"][2 * MB + 5] ^= 0xFF
            assert engine.save_to_storage(2, state)
            assert engine.save_to_storage(3, state)
        finally:
            _close(engine, job)
        return state

    def test_corrupt_shared_stripe_quarantines_exactly_referencing_steps(
        self, job_name, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("DLROVER_TPU_CKPT_STRIPE_MB", "1")
        ckpt_dir = str(tmp_path / "ckpts")
        self._three_steps(ckpt_dir, job_name)
        # Flip a byte inside step 2's owned stripe — the bytes BOTH
        # step 2 and step 3 (via its reference) read through.
        bin2 = ckpt_persist.shard_bin_path(ckpt_dir, 2, 0)
        with open(bin2, "r+b") as f:
            f.seek(2 * MB + 999)
            b = f.read(1)
            f.seek(2 * MB + 999)
            f.write(bytes([b[0] ^ 0x01]))

        loader = CheckpointEngine(ckpt_dir, keep_latest=0, job=f"{job_name}-l")
        try:
            step, restored = loader.load(big_state(seed=1))
            # The fallback chain lands on the newest step with no damaged
            # dependencies: step 1.
            assert step == 1
            np.testing.assert_array_equal(
                restored["w"], big_state(4 * MB)["w"]
            )
            skipped = dict(loader.last_restore_stats["skipped"])
            assert set(skipped) == {3, 2}
        finally:
            _close(loader, f"{job_name}-l")
        st = PosixDiskStorage()
        assert ckpt_persist.is_quarantined(st, ckpt_dir, 3)
        assert ckpt_persist.is_quarantined(st, ckpt_dir, 2)
        assert not ckpt_persist.is_quarantined(st, ckpt_dir, 1)
        assert "stripe" in ckpt_persist.quarantine_reason(st, ckpt_dir, 3)

    def test_gc_pins_reference_closure_of_keepers(
        self, job_name, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("DLROVER_TPU_CKPT_STRIPE_MB", "1")
        ckpt_dir = str(tmp_path / "ckpts")
        state = self._three_steps(ckpt_dir, job_name)
        st = PosixDiskStorage()
        # keep_latest=1 keeps step 3 — whose stripes live in steps 1 and
        # 2's bins, so BOTH survive GC despite falling out of the window.
        ckpt_persist.gc_steps(st, ckpt_dir, keep_latest=1)
        assert _step_dirs(ckpt_dir) == [
            "checkpoint-1", "checkpoint-2", "checkpoint-3"
        ]
        # And the pinned layout actually restores.
        loader = CheckpointEngine(ckpt_dir, keep_latest=0, job=f"{job_name}-l")
        try:
            step, restored = loader.load(big_state(seed=1))
            assert step == 3
            np.testing.assert_array_equal(restored["w"], state["w"])
        finally:
            _close(loader, f"{job_name}-l")
        # A later self-contained step releases the pins: nothing kept
        # references 1..3 anymore, GC frees them.
        monkeypatch.setenv("DLROVER_TPU_CKPT_INCREMENTAL", "0")
        engine = CheckpointEngine(ckpt_dir, keep_latest=0, job=job_name)
        try:
            assert engine.save_to_storage(4, state)
        finally:
            _close(engine, job_name)
        ckpt_persist.gc_steps(st, ckpt_dir, keep_latest=1)
        assert _step_dirs(ckpt_dir) == ["checkpoint-4"]


# ---------------------------------------------------------------------------
# Broadcast + cross-topology restore on the 8-device CPU mesh
# ---------------------------------------------------------------------------


class TestCrossTopologyRestore:
    def _accelerate(self, spec, batch_rows=8):
        import dataclasses as dc

        import jax
        import jax.numpy as jnp
        import optax

        from dlrover_tpu.accel import auto_accelerate
        from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn

        cfg = dc.replace(GPTConfig.tiny(), dtype=jnp.float32)
        model = GPT(cfg)

        def token_loss(module, params, batch):
            return loss_fn(module.apply({"params": params}, batch), batch)

        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (batch_rows, 16), 0, cfg.vocab_size
        )
        res = auto_accelerate(
            model, optax.adamw(1e-3), tokens, token_loss, spec=spec
        )
        batch = __import__("jax").device_put(tokens, res.batch_sharding)
        return res, batch

    def _tree_allclose(self, a, b, **kw):
        import jax

        la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), **kw)

    def test_save_data4_restore_data3_then_regrow(self, job_name, tmp_path):
        """The acceptance drill's restore core: a {data:4} checkpoint
        re-slices onto {data:3}, replicas hydrate device-to-device (the
        storage tier sees each byte ~once, not once per replica), and
        the regrown {data:4} world loads the same bytes back."""
        import jax

        from dlrover_tpu.accel import ParallelSpec

        ckpt_dir = str(tmp_path / "ckpts")
        res4, _ = self._accelerate(ParallelSpec(data=4), batch_rows=8)
        # The initialized state is checkpoint-worthy as-is; skipping the
        # train step keeps res4.state undonated for the regrow below and
        # the test out of compile time (trajectory equivalence across a
        # shrink+regrow is test_rescale's drill).
        state = res4.state
        jax.block_until_ready(state)
        expect = jax.device_get(state)

        saver = CheckpointEngine(
            ckpt_dir, keep_latest=0, job=f"{job_name}-s",
            mesh_axes={"data": 4},
        )
        try:
            assert saver.save_to_storage(7, state)
        finally:
            _close(saver, f"{job_name}-s")

        # Shrink: restore the same catalog onto a {data:3} template.
        res3, _ = self._accelerate(ParallelSpec(data=3), batch_rows=6)
        st = CountingStorage(PosixDiskStorage())
        loader3 = CheckpointEngine(
            ckpt_dir, storage=st, keep_latest=0, job=f"{job_name}-3",
            replica_rank=0, replica_count=3, mesh_axes={"data": 3},
        )
        try:
            step, restored = loader3.load(res3.state)
            assert step == 7
            self._tree_allclose(restored, expect, rtol=0, atol=0)
            stats = loader3.last_restore_stats
            payload = stats["bytes"]
            # Broadcast restore: each persisted byte crosses the storage
            # boundary ~twice (stripe verify + block reads) regardless of
            # how many devices replicate it — never once per replica.
            assert 0 < stats["storage_read_bytes"] <= 2.5 * payload
            # Storage-boundary total = counted reader traffic + small
            # metadata (tracker, shard metas) — NOT payload × replicas.
            assert (
                stats["storage_read_bytes"]
                <= st.read_bytes_total
                <= stats["storage_read_bytes"] + (1 << 16)
            )
            assert stats["h2d_bytes"] > 0
            # Replicated leaves fan out device-to-device along data.
            assert stats["d2d_bytes"] > 0
        finally:
            _close(loader3, f"{job_name}-3")

        # Regrow: the same checkpoint hydrates the {data:4} world again.
        loader4 = CheckpointEngine(
            ckpt_dir, keep_latest=0, job=f"{job_name}-4",
            replica_rank=0, replica_count=4, mesh_axes={"data": 4},
        )
        try:
            step, restored = loader4.load(res4.state)
            assert step == 7
            self._tree_allclose(restored, expect, rtol=0, atol=0)
        finally:
            _close(loader4, f"{job_name}-4")

    def test_uncoverable_catalog_raises_topology_mismatch(
        self, job_name, tmp_path
    ):
        """When the persisted blocks genuinely can't tile the template
        (a shard's peers were never persisted), restore must name both
        topologies and refuse the fallback chain — an older step saved
        the same way has the same gap."""
        import jax

        from dlrover_tpu.accel import ParallelSpec

        ckpt_dir = str(tmp_path / "ckpts")
        res, _ = self._accelerate(ParallelSpec(fsdp=4), batch_rows=8)
        saver = CheckpointEngine(
            ckpt_dir, keep_latest=0, job=f"{job_name}-s",
            mesh_axes={"data": 4},
        )
        try:
            assert saver.save_to_storage(3, res.state)
        finally:
            _close(saver, f"{job_name}-s")
        # Amputate part of one leaf's block coverage, the on-disk shape
        # of "this topology's peer shards are not in the checkpoint".
        meta_path = os.path.join(
            ckpt_persist.step_dir(ckpt_dir, 3), "shard_0.meta"
        )
        meta = pickle.loads(open(meta_path, "rb").read())
        multi = [
            p for p in {t.path for t in meta.tensors}
            if sum(t.path == p for t in meta.tensors) > 1
        ]
        assert multi, "fsdp=4 state should have multi-block leaves"
        victim = sorted(multi)[0]
        dropped = next(t for t in meta.tensors if t.path == victim)
        meta.tensors = [t for t in meta.tensors if t is not dropped]
        open(meta_path, "wb").write(pickle.dumps(meta))

        loader = CheckpointEngine(
            ckpt_dir, keep_latest=0, job=f"{job_name}-l",
            mesh_axes={"data": 3},
        )
        try:
            with pytest.raises(ckpt_persist.TopologyMismatchError) as ei:
                loader.load(res.state)
            msg = str(ei.value)
            assert "data" in msg and "step 3" in msg
        finally:
            _close(loader, f"{job_name}-l")
        # No silent fallback, no quarantine: the step on disk is intact.
        assert not ckpt_persist.is_quarantined(
            PosixDiskStorage(), ckpt_dir, 3
        )

    def test_rescale_hydrate_nacks_on_topology_mismatch(self):
        """RescaleEngine._hydrate converts the structural restore errors
        into RescaleInfeasible (a nack) so the master falls back to the
        legacy restart instead of burying the reason."""
        from dlrover_tpu.train.rescale import RescaleEngine, RescaleInfeasible

        class _Ckpt:
            last_restore_stats = {}

            def load(self, template):
                raise ckpt_persist.TopologyMismatchError(
                    7, {"data": 4}, {"data": 3}, "blocks cover 1/2"
                )

        eng = RescaleEngine.__new__(RescaleEngine)
        eng.checkpointer = _Ckpt()
        plan = m.RescalePlan(snapshot_step=7)
        with pytest.raises(RescaleInfeasible, match="re-sliced"):
            eng._hydrate(plan, template={"w": np.zeros(4)})


# ---------------------------------------------------------------------------
# Staging throughput + observability plumbing
# ---------------------------------------------------------------------------


class TestStagingAndGauges:
    def test_staging_emits_chunked_throughput_event(
        self, job_name, tmp_path
    ):
        """Satellite: D2H staging goes through the chunked fastcopy-pool
        fetch and reports per-op throughput, so a slow staging path is
        attributable (ckpt_staging_mbps vs d2h_probe_mbps)."""
        import jax.numpy as jnp

        from dlrover_tpu.observability import events as ev_mod

        seen = []
        sink = seen.append
        ev_mod.install_sink(sink)
        engine = CheckpointEngine(
            str(tmp_path / "ckpts"), keep_latest=0, job=job_name
        )
        try:
            state = {"w": jnp.zeros((4 * MB // 4,), dtype=jnp.float32)}
            assert engine.save_to_storage(1, state)
            ev_mod.flush_events()
            staging = [
                e for e in seen
                if e.kind == ev_mod.EventKind.CKPT_IO
                and e.args.get("op") == "staging"
            ]
            assert staging, "save must emit a ckpt.io staging event"
            ev = staging[-1]
            assert ev.args["bytes"] >= 4 * MB
            assert ev.args["mbps"] > 0
            assert ev.args["chunks"] >= 1
        finally:
            ev_mod.uninstall_sink(sink)
            _close(engine, job_name)

    def test_plane_exports_per_op_byte_gauges(self):
        import time

        from dlrover_tpu.observability.events import EventKind, JobEvent
        from dlrover_tpu.observability.plane import ObservabilityPlane

        plane = ObservabilityPlane()
        now = time.time()
        for op, nbytes, written in (
            ("persist", 64 * MB, 8 * MB),
            ("persist-skip", 0, 0),
        ):
            plane.event_log.append(JobEvent(
                kind=EventKind.CKPT_IO, ts=now, node_id=0, role="worker",
                args={
                    "op": op, "bytes": nbytes, "written_bytes": written,
                    "mbps": 100.0,
                },
            ), journal=False)
        by_name = {name: samples for name, _, _, samples
                   in plane.collect_metrics()}
        got = dict()
        for labels, val in by_name["dlrover_tpu_ckpt_io_bytes"]:
            got[labels["op"]] = val
        # The skip rides the gauge at 0 — the dedup cut is visible per
        # replica instead of reading as a missing scrape.
        assert got == {"persist": float(64 * MB), "persist-skip": 0.0}
        wrote = dict()
        for labels, val in by_name["dlrover_tpu_ckpt_io_written_bytes"]:
            wrote[labels["op"]] = val
        assert wrote["persist"] == float(8 * MB)
