"""KvVariable sparse embedding tests (SURVEY §2.6)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.sparse import KvVariable, SparseAdam


class TestKvVariable:
    def test_lookup_allocates_and_is_stable(self):
        var = KvVariable(dim=4, capacity=8, seed=1)
        ids = np.array([1001, 42, 1001])
        rows = np.asarray(var.lookup(ids))
        assert rows.shape == (3, 4)
        np.testing.assert_array_equal(rows[0], rows[2])  # same id, same row
        assert var.size == 2
        # A second lookup returns identical rows.
        np.testing.assert_array_equal(
            np.asarray(var.lookup(np.array([42]))), rows[1:2]
        )

    def test_growth_beyond_capacity(self):
        var = KvVariable(dim=2, capacity=4)
        var.lookup(np.arange(100))
        assert var.size == 100
        assert var.capacity >= 100
        assert var.table.shape[0] == var.capacity

    def test_unknown_id_without_allocate(self):
        var = KvVariable(dim=2, capacity=4)
        var.lookup(np.array([7]))
        before = var.size
        var.lookup(np.array([8, 9]), allocate=False)
        assert var.size == before  # inference never grows the table

    def test_batched_shape(self):
        var = KvVariable(dim=3, capacity=16)
        out = var.lookup(np.arange(6).reshape(2, 3))
        assert out.shape == (2, 3, 3)

    def test_row_grads_accumulate_duplicates(self):
        var = KvVariable(
            dim=2, capacity=4,
            initializer=lambda k, s, d: jnp.zeros(s, d),
        )
        ids = np.array([5, 5])
        grads = np.ones((2, 2))
        var.apply_row_grads(ids, grads, lr=0.1)
        row = np.asarray(var.lookup(np.array([5])))[0]
        np.testing.assert_allclose(row, -0.2 * np.ones(2), atol=1e-6)

    def test_export_import_roundtrip(self):
        var = KvVariable(dim=3, capacity=4, seed=3)
        var.lookup(np.array([10, 20, 30, 40, 50]))  # forces growth too
        ids, values = var.export()
        assert len(ids) == 5

        fresh = KvVariable(dim=3, capacity=2, seed=99)
        fresh.import_(ids, values)
        for i in ids:
            np.testing.assert_allclose(
                np.asarray(fresh.lookup(np.array([i]))),
                np.asarray(var.lookup(np.array([i]))),
                rtol=1e-6,
            )
        assert fresh.size == 5


class TestSparseAdam:
    def test_converges_per_key(self):
        """Each key's row converges to its own target; untouched keys
        never move."""
        var = KvVariable(
            dim=2, capacity=8,
            initializer=lambda k, s, d: jnp.zeros(s, d),
        )
        opt = SparseAdam(var, lr=0.05)
        targets = {7: np.array([1.0, -1.0]), 13: np.array([0.5, 2.0])}
        untouched = np.asarray(var.lookup(np.array([99])))  # allocate 99
        for _ in range(300):
            ids = np.array([7, 13])
            rows = np.asarray(var.lookup(ids))
            grads = 2 * (rows - np.stack([targets[7], targets[13]]))
            opt.update(ids, grads)
        for key, tgt in targets.items():
            got = np.asarray(var.lookup(np.array([key])))[0]
            np.testing.assert_allclose(got, tgt, atol=5e-2)
        np.testing.assert_array_equal(
            np.asarray(var.lookup(np.array([99]))), untouched
        )

    def test_state_grows_with_table(self):
        var = KvVariable(dim=2, capacity=2)
        opt = SparseAdam(var)
        opt.update(np.arange(10), np.ones((10, 2)))
        assert opt._m.shape[0] == var.capacity


class TestGrowMidTraining:
    """Growth during a jitted train loop must preserve
    optimizer slot values (the recompile-on-new-capacity path)."""

    def test_moments_survive_grow(self):
        var = KvVariable(dim=4, capacity=4, seed=1)
        adam = SparseAdam(var, lr=0.1)

        @jax.jit
        def fwd(table, slots):
            return jnp.take(table, slots, axis=0).sum()

        # Two Adam steps on key 0 BEFORE growth...
        g = np.ones((1, 4), np.float32)
        adam.update([0], g)
        adam.update([0], g)
        m_before = np.asarray(adam._m[var.to_slots([0])[0]]).copy()
        assert m_before.any()

        # ...touch enough new keys to force a capacity doubling, driving
        # the jitted gather through the recompile.
        for key in range(1, 9):
            slots = var.to_slots([key])
            fwd(var.table, jnp.asarray(slots))
            adam.update([key], g)
        assert var.capacity >= 16

        # key 0's moments and per-key step count survived intact.
        slot0 = var.to_slots([0], allocate=False)[0]
        np.testing.assert_allclose(
            np.asarray(adam._m[slot0]), m_before, rtol=1e-6
        )
        assert int(adam._counts[slot0]) == 2
        # a third step continues the same trajectory (bias correction
        # uses t=3, not t=1)
        adam.update([0], g)
        assert int(adam._counts[var.to_slots([0])[0]]) == 3


class TestHostSpillTier:
    """Tiered storage (parity: tfplus storage_table.h hybrid tables):
    cold rows spill to host RAM at max_capacity and restore on touch."""

    def test_capacity_capped_and_keys_preserved(self):
        var = KvVariable(dim=2, capacity=4, max_capacity=8, seed=0)
        written = {}
        for key in range(32):
            var.to_slots([key])
            row = np.full((1, 2), float(key), np.float32)
            var.scatter_update([key], row)
            written[key] = row[0]
        assert var.capacity == 8          # never grew past the cap
        assert var.resident_size == 8
        assert var.spilled_size == 24
        assert var.size == 32
        # every key's trained value is intact, wherever it lives
        for key, expect in written.items():
            np.testing.assert_allclose(
                np.asarray(var.lookup([key]))[0], expect
            )

    def test_lru_eviction_order(self):
        var = KvVariable(dim=2, capacity=2, max_capacity=2, seed=0)
        var.to_slots([1])
        var.to_slots([2])
        var.to_slots([1])          # 1 is now hottest
        var.to_slots([3])          # evicts 2 (coldest), not 1
        assert 1 in var._slots
        assert 3 in var._slots
        assert 2 in var._host_store

    def test_batch_larger_than_cap_raises(self):
        var = KvVariable(dim=2, capacity=2, max_capacity=2, seed=0)
        with pytest.raises(RuntimeError, match="max_capacity"):
            var.to_slots([1, 2, 3])

    def test_moments_survive_spill_and_restore(self):
        """An Adam trajectory split across an evict/restore must equal
        the uninterrupted one."""

        def train(max_capacity):
            var = KvVariable(dim=3, capacity=4, max_capacity=max_capacity,
                             seed=3)
            adam = SparseAdam(var, lr=0.05)
            g = np.ones((1, 3), np.float32) * 0.5
            adam.update([7], g)        # two steps on key 7
            adam.update([7], g)
            if max_capacity is not None:
                # flood with cold keys so 7 spills, moments included
                for key in range(100, 100 + max_capacity):
                    var.to_slots([key])
                assert 7 in var._host_store
            adam.update([7], g)        # third step after restore
            return np.asarray(var.lookup([7], allocate=False))[0]

        np.testing.assert_allclose(
            train(max_capacity=4), train(max_capacity=None), rtol=1e-6
        )

    def test_export_includes_spilled_rows(self):
        var = KvVariable(dim=2, capacity=2, max_capacity=2, seed=0)
        for key in range(6):
            var.scatter_update([key], np.full((1, 2), float(key)))
        ids, values = var.export()
        assert len(ids) == 6
        by_id = {int(k): v for k, v in zip(ids, values)}
        for key in range(6):
            np.testing.assert_allclose(by_id[key], [key, key])
        # round-trip through import_ on a fresh capped variable
        var2 = KvVariable(dim=2, capacity=2, max_capacity=4, seed=1)
        var2.import_(ids, values)
        assert var2.size == 6
        assert var2.capacity <= 4
        for key in range(6):
            np.testing.assert_allclose(
                np.asarray(var2.lookup([key], allocate=False))[0],
                [key, key],
            )


class TestImportSpillRestore:
    def test_import_seeded_restore_resets_stale_moments(self):
        """An import_()-seeded host-tier row (no optimizer payload)
        restoring onto a recycled slot must NOT inherit the evicted
        key's Adam moments (round-4 review finding)."""
        var = KvVariable(dim=2, capacity=2, max_capacity=2, seed=0)
        adam = SparseAdam(var, lr=0.1)
        # Seed 3 rows via import: 2 resident + 1 spilled (no payloads).
        ids = np.array([10, 11, 12], np.int64)
        values = np.array([[1, 1], [2, 2], [3, 3]], np.float32)
        var.import_(ids, values)
        assert var.spilled_size == 1
        # Build nonzero moments on a resident key...
        g = np.ones((1, 2), np.float32)
        adam.update([10], g)
        slot10 = var.to_slots([10], allocate=False)[0]
        assert np.asarray(adam._m[slot10]).any()
        # ...then touch key 12 (spilled, payload-less) and key 11 so the
        # hot key 10 gets evicted and 12 lands on its slot.
        var.to_slots([11])
        slots = var.to_slots([12])
        assert 10 in var._host_store
        # key 12's slot must carry ZERO moments, not key 10's.
        assert not np.asarray(adam._m[slots[0]]).any()
        assert int(adam._counts[slots[0]]) == 0
        # and key 10's moments survived the spill: restoring it brings
        # them back.
        slot10b = var.to_slots([10])[0]
        assert np.asarray(adam._m[slot10b]).any()


class TestGroupOptimizers:
    """Group-lasso sparse optimizers (SURVEY §2.6 group optimizers;
    parity: tfplus group_adam / group_adagrad)."""

    def test_group_lasso_zeroes_cold_rows(self):
        from dlrover_tpu.sparse.group_optimizers import SparseGroupLassoAdam

        var = KvVariable(dim=4, capacity=8, seed=0)
        opt = SparseGroupLassoAdam(var, lr=0.1, l21=5.0)
        # A strong regularizer against small gradients: rows shrink to 0.
        g = np.full((1, 4), 1e-3, np.float32)
        for _ in range(5):
            opt.update([7], g)
        assert 7 in set(opt.zero_rows([7, 8]))
        np.testing.assert_allclose(
            np.asarray(var.lookup([7], allocate=False))[0], 0.0,
            atol=1e-7,
        )

    def test_no_regularizer_matches_sparse_adam(self):
        from dlrover_tpu.sparse.group_optimizers import SparseGroupLassoAdam

        g = np.ones((1, 4), np.float32) * 0.3

        def train(cls, **kw):
            var = KvVariable(
                dim=4, capacity=8, seed=1,
                initializer=lambda k, s, d: jnp.zeros(s, d),
            )
            opt = cls(var, lr=0.05, **kw)
            for _ in range(3):
                opt.update([3], g)
            return np.asarray(var.lookup([3], allocate=False))[0]

        np.testing.assert_allclose(
            train(SparseGroupLassoAdam, l21=0.0),
            train(SparseAdam),
            rtol=1e-6,
        )

    def test_adagrad_converges_and_prox_applies(self):
        from dlrover_tpu.sparse.group_optimizers import SparseGroupAdagrad

        var = KvVariable(dim=2, capacity=4, seed=2)
        opt = SparseGroupAdagrad(var, lr=0.5)
        target = np.array([1.0, -2.0], np.float32)
        for _ in range(200):
            w = np.asarray(var.lookup([5]))[0]
            opt.update([5], (w - target)[None])
        np.testing.assert_allclose(
            np.asarray(var.lookup([5], allocate=False))[0], target,
            atol=0.05,
        )

    def test_adagrad_accumulator_survives_spill(self):
        from dlrover_tpu.sparse.group_optimizers import SparseGroupAdagrad

        def train(max_capacity):
            var = KvVariable(dim=2, capacity=4, max_capacity=max_capacity,
                             seed=3)
            opt = SparseGroupAdagrad(var, lr=0.2)
            g = np.ones((1, 2), np.float32)
            opt.update([9], g)
            opt.update([9], g)
            if max_capacity is not None:
                for key in range(100, 100 + max_capacity):
                    var.to_slots([key])
                assert 9 in var._host_store
            opt.update([9], g)
            return np.asarray(var.lookup([9], allocate=False))[0]

        np.testing.assert_allclose(
            train(4), train(None), rtol=1e-6
        )


class TestVocabChurnScale:
    """Realistic vocab churn (round-3 weak #9: 'no perf number for a
    realistic vocab churn'): tens of thousands of distinct ids stream
    through a capped table with an optimizer attached; the run must
    stay functional (exact spill/restore bookkeeping) and complete in
    bounded time thanks to the O(1)-victim LRU + batched tier moves."""

    # Promoted to slow: ~45s of pure churn volume; the same
    # spill/restore bookkeeping is asserted by the fast capped-table
    # tests above, this one only adds scale.
    @pytest.mark.slow
    def test_churn_through_capped_table(self):
        import time

        rng = np.random.default_rng(0)
        var = KvVariable(dim=8, capacity=1024, max_capacity=4096, seed=0)
        adam = SparseAdam(var, lr=0.01)
        n_steps, batch = 60, 256
        # Sentinel cold id: written once, then left to spill; its row
        # must come back byte-identical (the value-exactness check the
        # churn exists to exercise).
        sentinel = 999_999
        var.scatter_update([sentinel], np.full((1, 8), 7.5, np.float32))
        t0 = time.monotonic()
        seen = {sentinel}
        for step in range(n_steps):
            # zipf-ish skew: a hot head + a long cold tail, like vocab
            head = rng.integers(0, 2048, batch // 2)
            tail = rng.integers(2048, 20_000, batch // 2)
            ids = np.concatenate([head, tail])
            seen.update(int(i) for i in ids)
            g = rng.standard_normal((batch, 8)).astype(np.float32) * 0.01
            adam.update(ids, g)
        elapsed = time.monotonic() - t0
        assert var.capacity == 4096
        assert var.size == len(seen)
        assert var.resident_size <= 4096
        # the untouched sentinel genuinely went to the host tier...
        assert var.spilled_size > 0
        assert sentinel in var._host_store
        # ...and restores byte-exact through the batched tier moves
        np.testing.assert_array_equal(
            np.asarray(var.lookup([sentinel], allocate=False))[0],
            np.full(8, 7.5, np.float32),
        )
        # bounded wall time: generous ceiling (shared CI hosts run hot)
        # that an O(k*N) eviction regression still fails.
        assert elapsed < 300, f"churn took {elapsed:.1f}s"
        ids_, _ = var.export()
        assert len(ids_) == len(seen)


class TestDiskTier:
    """Third storage tier (parity: tfplus storage_table.h hybrid
    DRAM/SSD): device HBM > host RAM > disk, one lookup surface."""

    def test_three_tier_spill_and_restore(self, tmp_path):
        kv = KvVariable(dim=4, capacity=4, max_capacity=4,
                        host_capacity=3, disk_dir=str(tmp_path),
                        seed=1)
        # Touch 12 ids: 4 resident, 3 host, 5 on disk.
        first = {}
        for i in range(12):
            first[i] = np.asarray(kv.lookup([i]))[0].copy()
        assert kv.resident_size == 4
        assert kv.spilled_size == 8
        assert kv.disk_size == 5
        assert kv.size == 12
        # Every id restores bit-exact from whichever tier held it.
        for i in range(12):
            np.testing.assert_array_equal(
                np.asarray(kv.lookup([i]))[0], first[i]
            )

    def test_disk_rows_keep_their_values_through_updates(self, tmp_path):
        kv = KvVariable(dim=2, capacity=2, max_capacity=2,
                        host_capacity=1, disk_dir=str(tmp_path))
        kv.lookup([0, 1])
        kv.scatter_update([0, 1], np.array([[1., 1.], [2., 2.]]))
        kv.lookup([2, 3])   # 0,1 spill; one of them lands on disk
        kv.lookup([4, 5])   # deeper churn
        assert kv.disk_size >= 1
        np.testing.assert_array_equal(
            np.asarray(kv.lookup([0]))[0], [1., 1.]
        )
        np.testing.assert_array_equal(
            np.asarray(kv.lookup([1]))[0], [2., 2.]
        )

    def test_export_includes_disk_tier(self, tmp_path):
        kv = KvVariable(dim=2, capacity=2, max_capacity=2,
                        host_capacity=1, disk_dir=str(tmp_path))
        for i in range(8):
            kv.lookup([i])
        ids, values = kv.export()
        assert sorted(ids.tolist()) == list(range(8))
        kv2 = KvVariable(dim=2, capacity=2)
        kv2.import_(ids, values)
        for i, row in zip(ids, values):
            np.testing.assert_array_equal(
                np.asarray(kv2.lookup([int(i)]))[0], row
            )

    def test_optimizer_slots_survive_disk_trip(self, tmp_path):
        kv = KvVariable(dim=2, capacity=2, max_capacity=2,
                        host_capacity=1, disk_dir=str(tmp_path))
        opt = SparseAdam(kv, lr=0.1)
        ids = np.array([0, 1])
        kv.lookup(ids)
        opt.update(ids, np.ones((2, 2), np.float32))
        m_before = opt.extract_rows(kv.to_slots(ids))["m"].copy()
        # push 0 and 1 through host AND disk tiers
        kv.lookup([2, 3])
        kv.lookup([4, 5])
        assert kv.disk_size >= 1
        kv.lookup(ids)  # restore both
        m_after = opt.extract_rows(kv.to_slots(ids))["m"]
        np.testing.assert_allclose(m_after, m_before)

    def test_host_capacity_requires_disk_dir(self):
        with pytest.raises(ValueError, match="disk_dir"):
            KvVariable(dim=2, capacity=2, host_capacity=1)
