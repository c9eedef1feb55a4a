"""Elastic mesh reshape (ISSUE 16): searched specs + d2d resharding.

Covers the whole reshape plane: the shard-cover algebra's exactness
(exhaustive {data×tp}→{data'×tp'} transitions, brute-force masks as the
oracle), the constrained-world spec search (TP-for-accumulation trade,
stickiness), the RescalePlan spec schema, the master coordinator's spec
selection / journal / failover, the checkpoint engine's targeted region
reader, and the worker engine's hybrid d2d+snapshot hydration with its
torn-mix guard. The full GPT bit-identity drills (SIGKILL a {data×tp}
member, preemption notice on a TP member) are slow-marked.
"""

import dataclasses
import itertools
from dataclasses import asdict
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.accel import ParallelSpec
from dlrover_tpu.accel.search import (
    ModelProfile,
    search_reshape_spec,
    spec_diff,
    spec_from_dict,
    spec_move_distance,
)
from dlrover_tpu.common import messages as m
from dlrover_tpu.common import shard_cover as sc
from dlrover_tpu.common.batching import derive_accum_schedule
from dlrover_tpu.common.constants import RendezvousName
from dlrover_tpu.master.rescale import PLAN_ABORTED, PLAN_ISSUED
from dlrover_tpu.train.checkpoint.engine import CheckpointEngine
from dlrover_tpu.train.rescale import RescaleEngine

from tests.test_rescale import (
    TRAIN,
    formed_world,
    make_coordinator,
)

P = jax.sharding.PartitionSpec


def region_mask(shape, region):
    """Boolean mask of a region — the brute-force oracle."""
    mask = np.zeros(shape, dtype=bool)
    mask[tuple(slice(s, e) for s, e in region)] = True
    return mask


def dt_mesh(data, tensor):
    devs = np.array(jax.devices()[: data * tensor]).reshape(data, tensor)
    return jax.sharding.Mesh(devs, ("data", "tensor"))


# ---------------------------------------------------------------------------
# Region algebra: subtraction/intersection exactness
# ---------------------------------------------------------------------------


class TestRegionAlgebra:
    def test_subtract_exhaustive_1d(self):
        """Every interval pair in a small universe: the pieces are
        disjoint and union to the set difference exactly."""
        ivals = [
            (a, b) for a in range(5) for b in range(a + 1, 6)
        ]
        for region, hole in itertools.product(ivals, ivals):
            pieces = sc.subtract_region((region,), (hole,))
            got = np.zeros(6, dtype=int)
            for p in pieces:
                got[p[0][0]:p[0][1]] += 1
            want = region_mask((6,), (region,)) & ~region_mask((6,), (hole,))
            assert (got <= 1).all(), "overlapping pieces"
            np.testing.assert_array_equal(got.astype(bool), want)

    def test_subtract_2d_slabs(self):
        ivals = [(0, 2), (1, 3), (0, 4), (2, 4), (1, 2)]
        for r0, r1, h0, h1 in itertools.product(ivals, repeat=4):
            region, hole = (r0, r1), (h0, h1)
            pieces = sc.subtract_region(region, hole)
            got = np.zeros((4, 4), dtype=int)
            for p in pieces:
                got[tuple(slice(s, e) for s, e in p)] += 1
            want = (
                region_mask((4, 4), region) & ~region_mask((4, 4), hole)
            )
            assert (got <= 1).all()
            np.testing.assert_array_equal(got.astype(bool), want)

    def test_split_cover_partitions_destination(self):
        """d2d pieces land inside their claimed source, snapshot pieces
        outside every source, and together they tile dst exactly."""
        dst = ((0, 8), (0, 4))
        sources = [((0, 3), (0, 4)), ((2, 5), (1, 3)), ((6, 8), (0, 2))]
        split = sc.split_cover(dst, sources)
        counts = np.zeros((8, 4), dtype=int)
        for region, si in split.d2d:
            counts[tuple(slice(s, e) for s, e in region)] += 1
            assert sc.intersect_regions(region, sources[si]) == region
        for region in split.snapshot:
            counts[tuple(slice(s, e) for s, e in region)] += 1
            for src in sources:
                assert sc.intersect_regions(region, src) is None
        np.testing.assert_array_equal(
            counts, region_mask((8, 4), dst).astype(int)
        )
        assert split.d2d_elems + split.snapshot_elems == sc.region_size(dst)

    def test_empty_and_full_covers(self):
        dst = ((0, 4),)
        none = sc.split_cover(dst, [])
        assert none.d2d == () and none.snapshot == (dst,)
        full = sc.split_cover(dst, [((0, 4),)])
        assert full.snapshot == () and full.d2d == ((dst, 0),)


# ---------------------------------------------------------------------------
# Exhaustive {data×tp} -> {data'×tp'} cover intersections
# ---------------------------------------------------------------------------

_DT = [(1, 1), (2, 1), (1, 2), (2, 2), (4, 1), (1, 4), (4, 2), (2, 4),
       (8, 1), (1, 8)]


class TestCoverIntersectionExhaustive:
    """Every {data×tp}→{data'×tp'} pair over the 8 virtual devices, for
    an activation-style leaf (sharded over both axes) and a param-style
    leaf (tp-sharded, data-replicated). Oracle: brute-force element
    masks; the assembled bytes must be bitwise identical to a full
    snapshot restore (the saved array itself)."""

    def check_split(self, arr_np, old_sharding, new_sharding, lost):
        old = jax.device_put(arr_np, old_sharding)
        splits = sc.leaf_transfer_split(old, new_sharding, lost)
        donors = sc.surviving_shards(old, lost)
        donor_regions = [
            sc.normalize_index(d.index, old.shape) for d in donors
        ]
        survivor_mask = np.zeros(arr_np.shape, dtype=bool)
        for r in donor_regions:
            survivor_mask |= region_mask(arr_np.shape, r)
        total_d2d = total_snap = 0
        for dst, split in splits.items():
            counts = np.zeros(arr_np.shape, dtype=int)
            for region, si in split.d2d:
                counts[tuple(slice(s, e) for s, e in region)] += 1
                # every d2d piece must lie inside its donor
                assert sc.intersect_regions(
                    region, donor_regions[si]
                ) == region
            snap_mask = np.zeros(arr_np.shape, dtype=bool)
            for region in split.snapshot:
                counts[tuple(slice(s, e) for s, e in region)] += 1
                snap_mask |= region_mask(arr_np.shape, region)
            # exact tiling of the destination region
            np.testing.assert_array_equal(
                counts.astype(bool), region_mask(arr_np.shape, dst)
            )
            assert (counts <= 1).all()
            # the snapshot remainder is EXACTLY what no survivor covers
            np.testing.assert_array_equal(
                snap_mask, region_mask(arr_np.shape, dst) & ~survivor_mask
            )
            # bitwise assembly: d2d from donor buffers, snapshot from the
            # saved-array oracle — must reproduce the original exactly
            out = np.full(
                tuple(e - s for s, e in dst), np.nan, dtype=arr_np.dtype
            )
            base = tuple(s for s, _ in dst)
            for region, si in split.d2d:
                dsl = tuple(
                    slice(s - b, e - b) for (s, e), b in zip(region, base)
                )
                dreg = donor_regions[si]
                ssl = tuple(
                    slice(s - ds, e - ds)
                    for (s, e), (ds, _) in zip(region, dreg)
                )
                out[dsl] = np.asarray(donors[si].data)[ssl]
            for region in split.snapshot:
                dsl = tuple(
                    slice(s - b, e - b) for (s, e), b in zip(region, base)
                )
                out[dsl] = arr_np[tuple(slice(s, e) for s, e in region)]
            np.testing.assert_array_equal(
                out, arr_np[tuple(slice(s, e) for s, e in dst)]
            )
            total_d2d += split.d2d_elems
            total_snap += split.snapshot_elems
        return total_d2d, total_snap

    @pytest.mark.parametrize("new_dt", _DT)
    @pytest.mark.parametrize("old_dt", _DT)
    def test_all_transitions(self, old_dt, new_dt):
        (od, ot), (nd, nt) = old_dt, new_dt
        old_mesh, new_mesh = dt_mesh(od, ot), dt_mesh(nd, nt)
        # the highest member dies (one device per member)
        lost = [jax.devices()[od * ot - 1]] if od * ot > 1 else []
        arr = np.arange(16 * 8, dtype=np.float32).reshape(16, 8)
        for spec_old, spec_new in [
            (P("data", "tensor"), P("data", "tensor")),   # activation
            (P(None, "tensor"), P(None, "tensor")),       # param (dp-repl)
        ]:
            d2d, snap = self.check_split(
                arr,
                jax.sharding.NamedSharding(old_mesh, spec_old),
                jax.sharding.NamedSharding(new_mesh, spec_new),
                lost,
            )
            if not lost:
                assert snap == 0
            # replicated-over-data params survive a single death whenever
            # another data row holds the same tp shard
            if spec_old == P(None, "tensor") and od > 1:
                assert snap == 0

    def test_full_loss_goes_to_snapshot(self):
        """Kill EVERY holder of a shard: its whole region must come from
        the snapshot, and nothing else may."""
        mesh = dt_mesh(2, 2)
        shd = jax.sharding.NamedSharding(mesh, P(None, "tensor"))
        arr = np.arange(32, dtype=np.float32).reshape(4, 8)
        # tensor column 1 lives on devices (0,1) and (1,1) = flat 1 and 3
        lost = [jax.devices()[1], jax.devices()[3]]
        d2d, snap = self.check_split(
            arr, shd, jax.sharding.NamedSharding(dt_mesh(1, 2), shd.spec),
            lost,
        )
        assert snap == 16 and d2d == 16


# ---------------------------------------------------------------------------
# Constrained-world spec search
# ---------------------------------------------------------------------------


def compute_bound_profile():
    """A profile whose arithmetic dominates collectives, so the search
    legitimately wants every device it can get."""
    return ModelProfile(
        param_count=4_000_000, num_layers=4, d_model=256, ff_dim=1024,
        seq_len=128, vocab_size=512, num_heads=4,
        flops_per_token=6.0 * 4_000_000,
    )


class TestSearchReshapeSpec:
    def test_trades_tp_for_accumulation_on_shrink(self):
        prof = compute_bound_profile()
        cur = ParallelSpec(data=2, tensor=2)
        found = search_reshape_spec(
            prof, 3, 16, 16e9, current_spec=cur, peak_flops=1e9,
        )
        assert found is not None
        spec, est = found
        assert spec.total <= 3
        # 4 devices do not fit in 3: the transition must give something
        # up relative to {data=2, tensor=2}.
        assert spec != cur
        assert est.step_s > 0

    def test_uses_all_devices_when_they_divide(self):
        prof = compute_bound_profile()
        found = search_reshape_spec(
            prof, 4, 16, 16e9,
            current_spec=ParallelSpec(data=2, tensor=2), peak_flops=1e9,
        )
        assert found is not None and found[0].total == 4

    def test_stickiness_prefers_current_layout(self):
        """Among near-equal candidates the one moving the least state
        wins — with a huge stickiness window, the current spec itself."""
        prof = compute_bound_profile()
        cur = ParallelSpec(data=2, tensor=2)
        found = search_reshape_spec(
            prof, 4, 16, 16e9, current_spec=cur, peak_flops=1e9,
            stickiness=1e9,
        )
        assert found is not None
        assert spec_move_distance(cur, found[0]) == 0.0

    def test_no_devices_returns_none(self):
        assert search_reshape_spec(
            compute_bound_profile(), 0, 16, 16e9
        ) is None

    def test_spec_diff_and_roundtrip(self):
        a = ParallelSpec(data=2, tensor=2)
        b = ParallelSpec(data=4)
        assert spec_diff(a, b) == "data 2->4, tensor 2->1"
        assert spec_diff(a, a) == "unchanged"
        assert spec_diff(asdict(a), asdict(b)) == "data 2->4, tensor 2->1"
        # asdict round-trip, unknown keys dropped (journal forward-compat)
        d = asdict(a)
        d["someday_axis"] = 7
        assert spec_from_dict(d) == a

    def test_move_distance_data_is_free(self):
        a, b = ParallelSpec(data=2), ParallelSpec(data=4)
        assert spec_move_distance(a, b) == 0.0
        assert spec_move_distance(
            ParallelSpec(data=2, fsdp=2), ParallelSpec(data=4, tensor=1)
        ) == 1.0


# ---------------------------------------------------------------------------
# Plan schema
# ---------------------------------------------------------------------------


class TestPlanSpecSchema:
    def test_defaults_do_not_reshape(self):
        plan = m.RescalePlan()
        assert plan.old_spec == {} and plan.new_spec == {}
        assert not plan.reshapes

    def test_reshapes_iff_new_differs(self):
        a, b = asdict(ParallelSpec(data=2)), asdict(ParallelSpec(fsdp=2))
        assert m.RescalePlan(old_spec=a, new_spec=b).reshapes
        assert not m.RescalePlan(old_spec=a, new_spec=dict(a)).reshapes
        # a plan that never searched stays a plain DP retune
        assert not m.RescalePlan(old_spec=a).reshapes

    def test_journal_roundtrip(self):
        plan = m.RescalePlan(
            plan_id=7, old_spec=asdict(ParallelSpec(data=2, tensor=2)),
            new_spec=asdict(ParallelSpec(data=2)),
        )
        back = m.RescalePlan(**dataclasses.asdict(plan))
        assert back.reshapes and back.new_spec == plan.new_spec


# ---------------------------------------------------------------------------
# Master coordinator: spec selection, journal, failover
# ---------------------------------------------------------------------------


def tiny_parallel_config():
    from dlrover_tpu.models.gpt import GPTConfig

    return (
        asdict(ParallelSpec(data=2, tensor=2)),
        asdict(ModelProfile.from_config(GPTConfig.tiny())),
        16e9,
    )


class TestCoordinatorReshape:
    def test_plan_carries_searched_spec(self):
        mgr, round_, world = formed_world(4)
        coord = make_coordinator(mgr)
        spec_d, prof_d, hbm = tiny_parallel_config()
        coord.set_parallel_config(spec_d, prof_d, hbm)
        plan = coord.on_node_removed(3, dict(world))
        assert plan is not None
        assert plan.old_spec == spec_d
        assert plan.new_spec, "coordinator should have searched a spec"
        new_sp = spec_from_dict(plan.new_spec)
        assert new_sp.total <= 3
        assert plan.reshapes

    def test_no_parallel_config_stays_dp_only(self):
        mgr, round_, world = formed_world(4)
        coord = make_coordinator(mgr)
        plan = coord.on_node_removed(3, dict(world))
        assert plan is not None
        assert plan.old_spec == {} and plan.new_spec == {}
        assert not plan.reshapes

    def test_non_integral_member_mapping_stays_dp_only(self):
        """5 devices over 4 members has no per-member device slice:
        nothing principled to search against."""
        mgr, round_, world = formed_world(4)
        coord = make_coordinator(mgr)
        spec_d, prof_d, hbm = tiny_parallel_config()
        spec_d = asdict(ParallelSpec(data=5))
        coord.set_parallel_config(spec_d, prof_d, hbm)
        plan = coord.on_node_removed(3, dict(world))
        assert plan is not None and not plan.reshapes

    def test_reshape_knob_off_stays_dp_only(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_RESCALE_RESHAPE", "0")
        mgr, round_, world = formed_world(4)
        coord = make_coordinator(mgr)
        spec_d, prof_d, hbm = tiny_parallel_config()
        coord.set_parallel_config(spec_d, prof_d, hbm)
        plan = coord.on_node_removed(3, dict(world))
        assert plan is not None and not plan.reshapes

    def test_config_replay_restores_search_inputs(self):
        """A failed-over master replays the ("reshape", config) record
        and can search the NEXT transition."""
        mgr, round_, world = formed_world(4)
        coord = make_coordinator(mgr)
        spec_d, prof_d, hbm = tiny_parallel_config()
        coord.replay_reshape({
            "rec": "config", "spec": spec_d, "profile": prof_d,
            "hbm": hbm,
        })
        plan = coord.on_node_removed(3, dict(world))
        assert plan is not None and plan.reshapes

    def test_checkpoint_restore_roundtrip(self):
        mgr, round_, world = formed_world(4)
        coord = make_coordinator(mgr)
        spec_d, prof_d, hbm = tiny_parallel_config()
        coord.set_parallel_config(spec_d, prof_d, hbm)
        snap = coord.checkpoint()
        assert snap["spec"] == spec_d

        mgr2, _, world2 = formed_world(4)
        coord2 = make_coordinator(mgr2)
        coord2.restore(snap)
        plan = coord2.on_node_removed(3, dict(world2))
        assert plan is not None and plan.reshapes

    def test_nack_aborts_and_remembers_diff(self):
        mgr, round_, world = formed_world(4)
        coord = make_coordinator(mgr)
        spec_d, prof_d, hbm = tiny_parallel_config()
        coord.set_parallel_config(spec_d, prof_d, hbm)
        plan = coord.on_node_removed(3, dict(world))
        assert plan.reshapes
        select = dict(coord._last_select)
        assert select["plan_id"] == plan.plan_id
        assert select["diff"] and select["diff"] != "unchanged"
        coord.apply_ack(
            plan.plan_id, 0,
            ok=False, error="plan 1 (round 2, data 2->1): boom",
        )
        got = coord.get_plan(TRAIN, 0, 0)
        assert not got.exists or got.status == PLAN_ABORTED


# ---------------------------------------------------------------------------
# Engine region reader
# ---------------------------------------------------------------------------


class TestMemoryRegionReader:
    def test_reads_exact_regions_across_blocks(self, job_name, tmp_path):
        from dlrover_tpu.common.ckpt_meta import ckpt_shm_name
        from dlrover_tpu.common.shared_memory import SharedMemory

        mesh = dt_mesh(4, 1)
        shd = jax.sharding.NamedSharding(mesh, P("data", None))
        w = np.arange(64, dtype=np.float32).reshape(8, 8)
        state = {"w": jax.device_put(w, shd), "step": np.int64(5)}
        eng = CheckpointEngine(str(tmp_path / "ck"), keep_latest=0)
        try:
            assert eng.save_to_memory(5, state, block=True)
            step, read = eng.memory_region_reader()
            assert step == 5 and read is not None
            # a region crossing two of the four saved blocks
            got = read("['w']", ((1, 5), (2, 7)))
            np.testing.assert_array_equal(got, w[1:5, 2:7])
            with pytest.raises(KeyError):
                read("['nope']", ((0, 1),))
        finally:
            eng.close()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))

    def test_no_snapshot_returns_none(self, job_name, tmp_path):
        eng = CheckpointEngine(str(tmp_path / "ck"), keep_latest=0)
        try:
            step, read = eng.memory_region_reader()
            assert step == -1 and read is None
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# Worker engine: hybrid hydration
# ---------------------------------------------------------------------------


class FakeSpecHost:
    """The minimum `host` contract, with spec-aware retune: rebuilds an
    fsdp mesh + shardings + throwaway state for the requested spec."""

    def __init__(self, shape=(8, 4)):
        self.shape = shape
        self.result = None
        self.retunes = []

    def _build(self, spec):
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[: spec.total]), ("fsdp",)
        )
        shardings = {
            "w": jax.sharding.NamedSharding(mesh, P("fsdp", None)),
            "step": jax.sharding.NamedSharding(mesh, P()),
        }
        state = {
            "w": jax.device_put(
                np.zeros(self.shape, np.float32), shardings["w"]
            ),
            "step": jax.device_put(np.int64(0), shardings["step"]),
        }
        self.result = SimpleNamespace(
            spec=spec, mesh=mesh, state=state, shardings=shardings,
            batch_sharding=None,
        )

    def retune(self, world_size, rank=None, spec=None):
        self.retunes.append((world_size, rank, spec))
        if spec is not None:
            self._build(spec)


def reshape_plan(old_spec, new_spec, snapshot_step, new_nodes=3):
    sched = derive_accum_schedule(16, 4, new_nodes)
    return m.RescalePlan(
        plan_id=1, rdzv_name=RendezvousName.TRAINING, old_round=1,
        new_round=2, old_world={0: 1, 1: 1, 2: 1, 3: 1},
        new_world={r: 1 for r in range(new_nodes)}, global_batch=16,
        micro_batch=sched.micro_batch, accum_counts=list(sched.counts),
        snapshot_step=snapshot_step, status=PLAN_ISSUED,
        old_spec=asdict(old_spec), new_spec=asdict(new_spec),
    )


@pytest.fixture
def fsdp_world(job_name, tmp_path):
    """A live fsdp=4 state + warm shm snapshot + cleanup."""
    from dlrover_tpu.common.ckpt_meta import ckpt_shm_name
    from dlrover_tpu.common.shared_memory import SharedMemory

    host = FakeSpecHost()
    host._build(ParallelSpec(fsdp=4))
    w = np.arange(32, dtype=np.float32).reshape(8, 4)
    state = {
        "w": jax.device_put(w, host.result.shardings["w"]),
        "step": jax.device_put(np.int64(5), host.result.shardings["step"]),
    }
    host.result.state = state
    eng = CheckpointEngine(str(tmp_path / "ck"), keep_latest=0)
    assert eng.save_to_memory(5, state, block=True)
    try:
        yield host, state, w, eng
    finally:
        eng.close()
        SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))


class TestEngineHybridHydration:
    def test_d2d_plus_snapshot_bitwise(self, fsdp_world):
        """fsdp 4->2, member 3 dead: rows 0..5 flow d2d from survivors,
        rows 6..7 (the dead member's shard) from the snapshot — and the
        split is byte-exact."""
        host, state, w, ck = fsdp_world
        eng = RescaleEngine(host, node_rank=0, checkpointer=ck)
        eng.round = 1
        plan = reshape_plan(
            ParallelSpec(fsdp=4), ParallelSpec(fsdp=2), snapshot_step=5
        )
        tr = eng.apply(plan, state=state)
        assert tr.ok, tr.error
        assert tr.source == "live+snapshot"
        assert tr.spec_diff == "fsdp 4->2"
        assert tr.spec == ParallelSpec(fsdp=2)
        # w is (8, 4) f32: dead member held rows 6..7 = 8 elems = 32B;
        # rows 0..5 (24 elems = 96B) move d2d. step is unsharded.
        assert tr.snapshot_bytes == 32
        assert tr.d2d_bytes == 96
        np.testing.assert_array_equal(np.asarray(tr.state["w"]), w)
        assert int(tr.state["step"]) == 5
        # the rebuilt leaf really is laid out for the new spec
        assert tr.state["w"].sharding.is_equivalent_to(
            host.result.shardings["w"], 2
        )

    def test_all_covered_needs_no_snapshot(self, fsdp_world):
        """fsdp 4->1 with NO dead member (pure spec change, e.g. a grow
        rebalance): pure transfer_state, zero snapshot bytes."""
        host, state, w, ck = fsdp_world
        eng = RescaleEngine(host, node_rank=0, checkpointer=ck)
        eng.round = 1
        plan = reshape_plan(
            ParallelSpec(fsdp=4), ParallelSpec(fsdp=2), snapshot_step=5,
            new_nodes=4,
        )
        plan.new_world = dict(plan.old_world)
        sched = derive_accum_schedule(16, 4, 4)
        plan.micro_batch, plan.accum_counts = (
            sched.micro_batch, list(sched.counts),
        )
        tr = eng.apply(plan, state=state)
        assert tr.ok, tr.error
        assert tr.source == "live" and tr.snapshot_bytes == 0
        np.testing.assert_array_equal(np.asarray(tr.state["w"]), w)

    def test_torn_mix_nacks_with_round_and_diff(self, fsdp_world):
        """Snapshot at step 5, live state at step 6: splicing them would
        tear the state — the nack names the plan round and the attempted
        spec transition."""
        host, state, w, ck = fsdp_world
        state = dict(state)
        state["step"] = jax.device_put(
            np.int64(6), host.result.shardings["step"]
        )
        host.result.state = state
        eng = RescaleEngine(host, node_rank=0, checkpointer=ck)
        eng.round = 1
        plan = reshape_plan(
            ParallelSpec(fsdp=4), ParallelSpec(fsdp=2), snapshot_step=6
        )
        tr = eng.apply(plan, state=state)
        assert not tr.ok
        assert tr.error.startswith("plan 1 (round 2, fsdp 4->2):")
        assert "snapshot step 5" in tr.error and "6" in tr.error

    def test_dead_member_without_snapshot_nacks(self, job_name):
        host = FakeSpecHost()
        host._build(ParallelSpec(fsdp=4))
        w = np.arange(32, dtype=np.float32).reshape(8, 4)
        state = {
            "w": jax.device_put(w, host.result.shardings["w"]),
            "step": jax.device_put(
                np.int64(5), host.result.shardings["step"]
            ),
        }
        eng = RescaleEngine(host, node_rank=0, checkpointer=None)
        eng.round = 1
        plan = reshape_plan(
            ParallelSpec(fsdp=4), ParallelSpec(fsdp=2), snapshot_step=5
        )
        tr = eng.apply(plan, state=state)
        assert not tr.ok
        assert "plan 1 (round 2, fsdp 4->2)" in tr.error
        assert "no flash checkpoint engine" in tr.error

    def test_worker_knob_off_keeps_old_spec(self, fsdp_world, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_RESCALE_RESHAPE", "0")
        host, state, w, ck = fsdp_world
        eng = RescaleEngine(host, node_rank=0, checkpointer=ck)
        eng.round = 1
        plan = reshape_plan(
            ParallelSpec(fsdp=4), ParallelSpec(fsdp=2), snapshot_step=5
        )
        tr = eng.apply(plan, state=state)
        assert tr.ok, tr.error
        # retune ran WITHOUT a spec: the old mesh layout stays
        assert host.retunes[-1][2] is None
        assert host.result.spec == ParallelSpec(fsdp=4)
        np.testing.assert_array_equal(np.asarray(tr.state["w"]), w)


# ---------------------------------------------------------------------------
# Goodput evidence
# ---------------------------------------------------------------------------


class TestReshapeGoodputEvidence:
    def test_complete_folds_bytes_into_incident(self):
        from dlrover_tpu.observability.events import EventKind, JobEvent
        from dlrover_tpu.observability.goodput import GoodputLedger

        led = GoodputLedger(now=0.0)
        led.ingest(JobEvent(
            kind=EventKind.RESCALE_PLAN, ts=1.0, node_id=3,
            role="master", pid=0,
            args={"plan_id": 1, "spec_diff": "tensor 2->1"},
        ))
        led.ingest(JobEvent(
            kind=EventKind.RESCALE_COMPLETE, ts=2.0, node_id=3,
            role="worker", pid=0,
            args={
                "plan_id": 1, "spec_diff": "tensor 2->1",
                "d2d_bytes": 4096, "snapshot_bytes": 512,
            },
        ))
        inc = led.summary(now=3.0)["incidents"][0]
        assert inc["evidence"] == (
            "reshape tensor 2->1: d2d 4096B, snapshot 512B"
        )

    def test_plan_closed_by_the_next_step_books_under_rescale(self):
        """An in-place transition is its own cause: the time from the
        plan to the next step is booked under ``rescale``, not as a
        worker failure."""
        from dlrover_tpu.observability.events import EventKind, JobEvent
        from dlrover_tpu.observability.goodput import GoodputLedger

        led = GoodputLedger(now=0.0)
        led.note_step(5, ts=0.5)
        led.ingest(JobEvent(
            kind=EventKind.RESCALE_PLAN, ts=1.0,
            args={"plan_id": 1, "new_world": 3},
        ))
        led.note_step(6, ts=1.25)
        s = led.summary(now=1.25)
        assert s["incidents_by_cause"] == {"rescale": 1}
        assert s["downtime_by_cause_s"]["rescale"] == pytest.approx(0.25)
        assert s["open_incidents"] == 0

    def test_abort_folds_decline_reason(self):
        from dlrover_tpu.observability.events import EventKind, JobEvent
        from dlrover_tpu.observability.goodput import GoodputLedger

        led = GoodputLedger(now=0.0)
        led.ingest(JobEvent(
            kind=EventKind.RESCALE_PLAN, ts=1.0, node_id=3,
            role="master", pid=0, args={"plan_id": 1},
        ))
        led.ingest(JobEvent(
            kind=EventKind.RESCALE_ABORT, ts=2.0, node_id=3,
            role="master", pid=0,
            args={
                "plan_id": 1, "spec_diff": "fsdp 4->2",
                "reason": "snapshot stale",
            },
        ))
        inc = led.summary(now=3.0)["incidents"][0]
        assert inc["evidence"] == "reshape fsdp 4->2 declined: snapshot stale"


# ---------------------------------------------------------------------------
# Slow drills: the issue's acceptance chaos scenarios on a real GPT
# ---------------------------------------------------------------------------


def _gpt_world(world, spec, tmp_path):
    import optax

    from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn
    from dlrover_tpu.train.elastic_trainer import ElasticTrainer

    cfg = dataclasses.replace(GPTConfig.tiny(), dtype=jnp.float32)

    def token_loss(module, params, batch):
        return loss_fn(module.apply({"params": params}, batch), batch)

    micro = jax.random.randint(
        jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab_size
    )
    et = ElasticTrainer(global_batch_size=16, micro_batch_size=4,
                        world_size=world, rank=0)
    et.prepare(GPT(cfg), optax.adamw(1e-3), micro, token_loss, spec=spec)
    return et, cfg, micro, token_loss


def _train_steps(et, state, cfg, n, key=3):
    batch = jax.random.randint(
        jax.random.PRNGKey(key),
        (et.local_batch_size, 16), 0, cfg.vocab_size,
    )
    met = None
    for _ in range(n):
        state, met = et.result.train_step(
            state, jax.device_put(batch, et.result.batch_sharding)
        )
    return state, met


@pytest.mark.slow
@pytest.mark.chaos
class TestReshapeDrills:
    def test_sigkill_dt_member_reshapes_bit_identical(
        self, job_name, tmp_path
    ):
        """Acceptance drill 1: a {data=2 x tp=2} member dies; the master
        searches a spec for the 3 survivors, the engine reshapes in
        place, and one step later the loss is BIT-identical to the
        restart path hydrating from the same snapshot."""
        import optax

        from dlrover_tpu.accel.accelerate import transfer_state
        from dlrover_tpu.common.ckpt_meta import ckpt_shm_name
        from dlrover_tpu.common.shared_memory import SharedMemory
        from dlrover_tpu.models.gpt import GPT
        from dlrover_tpu.train.elastic_trainer import ElasticTrainer

        et, cfg, micro, token_loss = _gpt_world(
            4, ParallelSpec(data=2, tensor=2), tmp_path
        )
        state, _ = _train_steps(et, et.result.state, cfg, 2)
        et.result.state = state
        step0 = int(state["step"])
        saved = jax.tree_util.tree_map(
            lambda x: np.asarray(x).copy(), state
        )
        ck = CheckpointEngine(str(tmp_path / "ck"), keep_latest=0)
        try:
            assert ck.save_to_memory(step0, state, block=True)

            # Master side: the trainer's own reported config feeds the
            # search, exactly as _report_batch_config would.
            extras = et._parallel_config_extras()
            assert extras["parallel_spec"] == asdict(et.result.spec)
            mgr, round_, world = formed_world(4)
            coord = make_coordinator(mgr)
            coord.set_parallel_config(
                extras["parallel_spec"], extras["model_profile"],
                extras["hbm"],
            )
            plan = coord.on_node_removed(3, dict(world))  # SIGKILL'd
            assert plan is not None and plan.reshapes
            searched = spec_from_dict(plan.new_spec)
            assert searched.total <= 3

            eng = RescaleEngine(et, node_rank=0, checkpointer=ck)
            eng.round = round_
            tr = eng.apply(plan, state=state)
            assert tr.ok, tr.error
            assert tr.spec == searched and et.result.spec == searched
            # zero lost steps: the live step counter survived the move
            assert int(tr.state["step"]) == step0
            post = jax.tree_util.tree_leaves(tr.state)
            for x, y in zip(jax.tree_util.tree_leaves(saved), post):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

            # Restart-path oracle under the SAME searched spec.
            et_r = ElasticTrainer(global_batch_size=16, micro_batch_size=4,
                                  world_size=3, rank=0)
            et_r.prepare(GPT(cfg), optax.adamw(1e-3), micro, token_loss,
                         spec=searched)
            rstate = transfer_state(saved, et_r.result.shardings)
            s_ip, m_ip = _train_steps(et, tr.state, cfg, 1, key=4)
            s_rs, m_rs = _train_steps(et_r, rstate, cfg, 1, key=4)
            assert float(m_ip["loss"]) == float(m_rs["loss"]), (
                "in-place reshape diverged from the restart path"
            )
            for x, y in zip(
                jax.tree_util.tree_leaves(s_ip),
                jax.tree_util.tree_leaves(s_rs),
            ):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        finally:
            ck.close()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))

    def test_preempt_notice_on_tp_member_zero_lost_steps(
        self, job_name, tmp_path
    ):
        """Acceptance drill 2: a preemption notice lands on a TP member;
        the proactive shrink plan carries a searched spec, the engine
        reshapes at the step boundary, and no step is lost."""
        from dlrover_tpu.common.ckpt_meta import ckpt_shm_name
        from dlrover_tpu.common.shared_memory import SharedMemory
        from tests.test_preempt import make_preempt, notice_req

        et, cfg, micro, token_loss = _gpt_world(
            4, ParallelSpec(data=2, tensor=2), tmp_path
        )
        state, _ = _train_steps(et, et.result.state, cfg, 2)
        et.result.state = state
        step0 = int(state["step"])
        ck = CheckpointEngine(str(tmp_path / "ck"), keep_latest=0)
        try:
            assert ck.save_to_memory(step0, state, block=True)
            extras = et._parallel_config_extras()
            mgr, round_, world = formed_world(4)
            coord = make_coordinator(mgr)
            coord.set_parallel_config(
                extras["parallel_spec"], extras["model_profile"],
                extras["hbm"],
            )
            pre = make_preempt(mgr, rescale=coord)
            assert pre.on_notice(notice_req(3)).success
            pre.note_step(step0)  # step boundary -> proactive shrink
            plan = coord.get_plan(TRAIN, 0, round_)
            assert plan.exists and plan.reshapes

            eng = RescaleEngine(et, node_rank=0, checkpointer=ck)
            eng.round = round_
            tr = eng.apply(plan, state=state)
            assert tr.ok, tr.error
            assert int(tr.state["step"]) == step0, "lost steps"
            # training continues under the searched spec immediately
            s1, m1 = _train_steps(et, tr.state, cfg, 1, key=5)
            assert int(s1["step"]) == step0 + 1
            assert np.isfinite(float(m1["loss"]))
        finally:
            ck.close()
            SharedMemory.remove(ckpt_shm_name(job_name, 0, 0))
