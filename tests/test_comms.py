"""Link-aware communication plane: aggregator, strategy search, overlap,
governor.

Tier-1 coverage for the probe→decision comms loop: the master-side
LinkProfileAggregator (fleet folding, transfer-sample exclusion,
saturation hysteresis with frozen baseline, per-axis profile, kv
publication surviving failover), the measured-bandwidth strategy search
(bandwidth-optimal ring chosen on fast links, latency-optimal
hierarchical collectives chosen only on slow measured links, default
pricing byte-identical to the pre-profile model), backward-overlap
bit-identity (the overlapped train step's loss trajectory exactly
matches the serialized one), and the worker-side CommsGovernor (bounded
staging/readback deferral off the kv profile, checkpoint-engine
staging-defer routing, and the end-to-end chaos drill: an injected
``probe.link degrade`` flips the published profile to saturated and the
governor starts deferring).
"""

import dataclasses
import json

import jax
import numpy as np
import optax
import pytest

from dlrover_tpu.accel import ParallelSpec, auto_accelerate
from dlrover_tpu.accel.search import (
    ModelProfile,
    estimate,
    search_spec,
    spec_diff,
    spec_from_dict,
)
from dlrover_tpu.agent.device_check import LinkProbe
from dlrover_tpu.chaos.injector import (
    CHAOS_ENV,
    FaultEvent,
    FaultInjector,
    FaultPlan,
)
from dlrover_tpu.master.kv_store import KVStoreService
from dlrover_tpu.master.monitor.link_profile import (
    LINK_PROFILE_KV_KEY,
    LinkProfileAggregator,
)
from dlrover_tpu.models.gpt import GPT, GPTConfig, loss_fn
from dlrover_tpu.observability import events as events_mod
from dlrover_tpu.observability.event_log import EventLog
from dlrover_tpu.observability.events import EventKind, emit
from dlrover_tpu.train.comms import (
    CommsGovernor,
    get_governor,
    install_governor,
)


@pytest.fixture(autouse=True)
def _clean_routing_and_chaos(monkeypatch):
    """No leaked event sink/identity, chaos plan, or governor singleton."""
    monkeypatch.delenv(CHAOS_ENV, raising=False)
    FaultInjector.reset()
    events_mod.reset()
    install_governor(None)
    yield
    install_governor(None)
    events_mod.reset()
    FaultInjector.reset()


def _arm(monkeypatch, plan: FaultPlan):
    monkeypatch.setenv(CHAOS_ENV, plan.to_json())
    FaultInjector.reset()


PROBE_OK = {"h2d_mbps": 800.0, "d2h_mbps": 800.0, "rtt_ms": 1.0}
PROBE_SLOW = {"h2d_mbps": 40.0, "d2h_mbps": 40.0, "rtt_ms": 20.0}


def _agg(**kw):
    kw.setdefault("window", 8)
    kw.setdefault("saturation_ratio", 0.5)
    kw.setdefault("sustain", 2)
    kw.setdefault("publish_every_s", 0.0)
    return LinkProfileAggregator(**kw)


def _feed(agg, samples_by_node, **extra):
    for node_id, sample in samples_by_node.items():
        emit(EventKind.PROBE_LINK, _node_id=node_id, _role="agent",
             **sample, **extra)


class _KvClient:
    """MasterClient stand-in: kv_store_get straight off a KVStoreService."""

    def __init__(self, kv):
        self.kv = kv

    def kv_store_get(self, key):
        return self.kv.get(key)


class TestLinkProfileAggregator:
    def _wire(self, **kw):
        log = EventLog()
        events_mod.install_sink(log.append)
        agg = _agg(**kw)
        log.add_listener(agg.observe)
        return log, agg

    def test_fleet_fold_medians_and_min(self):
        _, agg = self._wire()
        _feed(agg, {
            0: dict(PROBE_OK, d2h_mbps=600.0),
            1: dict(PROBE_OK, d2h_mbps=800.0),
            2: dict(PROBE_OK, d2h_mbps=1000.0),
        })
        agg.tick(now=1.0)
        fleet = agg.profile()["fleet"]
        assert fleet["nodes"] == 3
        assert fleet["d2h_mbps_median"] == 800.0
        assert fleet["d2h_mbps_min"] == 600.0
        assert fleet["rtt_ms_median"] == 1.0
        assert fleet["saturated"] is False
        m = {name: rows for name, _t, _h, rows in agg.metrics()}
        assert (None, 3.0) in m["dlrover_tpu_comms_tracked_nodes"]
        assert ({"link": "d2h_mbps", "stat": "min"}, 600.0) in \
            m["dlrover_tpu_comms_link_mbps"]

    def test_transfer_flagged_samples_excluded(self):
        _, agg = self._wire()
        _feed(agg, {0: PROBE_SLOW}, transfer=True)
        agg.tick(now=1.0)
        assert agg.profile() == {}  # nothing folded: no untainted samples
        _feed(agg, {0: PROBE_OK})
        _feed(agg, {0: PROBE_SLOW}, transfer=True)
        agg.tick(now=2.0)
        # Only the untainted sample is in the ring — a d2d transfer's
        # depressed bandwidth must not poison the saturation baseline.
        assert agg.profile()["fleet"]["d2h_mbps_median"] == 800.0

    def test_probe_transfer_window_flags_samples(self):
        log, agg = self._wire()
        events_mod.set_identity(0, "agent")
        probe = LinkProbe(interval=0, busy_fn=lambda: False,
                          sample_fn=lambda: dict(PROBE_OK))
        with LinkProbe.transfer_window():
            assert LinkProbe.transfer_active()
            probe.sample_once()
        assert not LinkProbe.transfer_active()
        probe.sample_once()
        flagged, clean = log.events(kinds=[EventKind.PROBE_LINK])
        assert flagged.args.get("transfer") is True
        assert "transfer" not in clean.args
        agg.tick(now=1.0)
        ring = agg._nodes[0]
        assert ring.samples_seen == 1  # the in-transfer sample dropped

    def test_saturation_hysteresis_and_frozen_baseline(self):
        log, agg = self._wire()
        now = 0.0
        for _ in range(4):  # healthy baseline
            now += 1.0
            _feed(agg, {0: PROBE_OK, 1: PROBE_OK})
            agg.tick(now=now)
        assert not agg.saturated()
        for _ in range(4):  # sustained degradation → flag
            now += 1.0
            _feed(agg, {0: PROBE_SLOW, 1: PROBE_SLOW})
            agg.tick(now=now)
        assert agg.saturated()
        assert log.events(kinds=[EventKind.COMMS_SATURATED])
        assert not log.events(kinds=[EventKind.COMMS_CLEARED])
        # Stays flagged while degraded — the baseline is frozen at its
        # healthy value, so the degraded window cannot re-baseline.
        for _ in range(6):
            now += 1.0
            _feed(agg, {0: PROBE_SLOW, 1: PROBE_SLOW})
            agg.tick(now=now)
        assert agg.saturated()
        for _ in range(4):  # sustained recovery → clear
            now += 1.0
            _feed(agg, {0: PROBE_OK, 1: PROBE_OK})
            agg.tick(now=now)
        assert not agg.saturated()
        assert len(log.events(kinds=[EventKind.COMMS_CLEARED])) == 1
        assert len(log.events(kinds=[EventKind.COMMS_SATURATED])) == 1

    def test_axis_profile_prices_crossing_axes_only(self):
        _, agg = self._wire()
        agg.set_axis_links({"data": True, "fsdp": False})
        _feed(agg, {
            0: dict(PROBE_OK, d2h_mbps=500.0, rtt_ms=2.0),
            1: dict(PROBE_OK, d2h_mbps=700.0, rtt_ms=4.0),
        })
        agg.tick(now=1.0)
        axes = agg.search_profile()
        # Crossing axis: conservative fleet-min bandwidth, median RTT.
        assert axes["data"]["kind"] == "dcn"
        assert axes["data"]["bw_bytes_s"] == 500.0 * 1e6
        assert axes["data"]["lat_s"] == pytest.approx(3.0e-3)
        # Host-local axis: analytic fallback (nulls), flag still carried.
        assert axes["fsdp"]["kind"] == "ici"
        assert axes["fsdp"]["bw_bytes_s"] is None
        assert axes["fsdp"]["saturated"] is False

    def test_remove_worker_drops_node(self):
        _, agg = self._wire()
        _feed(agg, {0: PROBE_OK, 1: dict(PROBE_OK, d2h_mbps=100.0)})
        agg.remove_worker(1)
        agg.tick(now=1.0)
        fleet = agg.profile()["fleet"]
        assert fleet["nodes"] == 1 and fleet["d2h_mbps_min"] == 800.0

    def test_kv_publish_survives_failover(self):
        kv = KVStoreService()
        log = EventLog()
        events_mod.install_sink(log.append)
        agg = _agg(kv_store=kv)
        log.add_listener(agg.observe)
        now = 0.0
        for sample in (PROBE_OK,) * 4 + (PROBE_SLOW,) * 4:
            now += 1.0
            _feed(agg, {0: sample, 1: sample})
            agg.tick(now=now)
        assert agg.saturated()
        profile = json.loads(kv.get(LINK_PROFILE_KV_KEY).decode())
        assert profile["fleet"]["saturated"] is True
        assert profile["axes"]["data"]["saturated"] is True
        # Failover: the kv store rides master snapshots — a promoted
        # standby restores the same bytes and the governor's next
        # refresh sees the same verdict with no re-measurement.
        standby = KVStoreService()
        standby.restore_state(kv.export_state())
        gov = CommsGovernor(client=_KvClient(standby), refresh_s=0.0)
        assert gov.saturated() is True
        assert log.events(kinds=[EventKind.COMMS_PROFILE])


FAST_LINK = {a: {"bw_bytes_s": 9e10, "lat_s": 5e-6, "saturated": False}
             for a in ("data", "fsdp")}
SLOW_LINK = {a: {"bw_bytes_s": 1e9, "lat_s": 1e-4, "saturated": True}
             for a in ("data", "fsdp")}


class TestStrategySearch:
    """Golden directions for the measured-bandwidth collective search."""

    def _profile(self):
        return ModelProfile(
            param_count=100_000_000, num_layers=4, d_model=512,
            ff_dim=2048, seq_len=512, vocab_size=1024, num_heads=8,
            flops_per_token=6e8,
        )

    def _search(self, link_profile):
        return search_spec(
            self._profile(), 8, 64, 16e9, devices_per_host=4,
            link_profile=link_profile, strategies=True,
        )

    def test_fast_links_keep_bandwidth_optimal_ring(self):
        spec, _ = self._search(FAST_LINK)[0]
        assert spec.collectives == ()

    def test_slow_measured_link_switches_to_latency_optimal(self):
        ranked = self._search(SLOW_LINK)
        spec, best = ranked[0]
        assert dict(spec.collectives) == {"data": "lat"}
        # ...and it wins on the model's own terms: the serialized-ring
        # pricing of the same mesh shape is strictly slower.
        serial = [e for s, e in ranked
                  if s.data == spec.data and s.fsdp == spec.fsdp
                  and s.collectives == ()]
        assert serial and serial[0].step_s > best.step_s

    def test_default_pricing_unchanged_without_profile(self):
        """The "bw" strategy and the absent entry are the same model —
        calibration goldens elsewhere must not move."""
        p = self._profile()
        base = ParallelSpec(data=4, fsdp=2)
        tagged = dataclasses.replace(
            base, collectives={"data": "bw", "fsdp": "bw"}
        )
        a = estimate(p, base, 64, 16e9, devices_per_host=4)
        b = estimate(p, tagged, 64, 16e9, devices_per_host=4)
        assert a.step_s == b.step_s
        assert a.comm_overlap_s == b.comm_overlap_s
        assert a.comm_critical_s == b.comm_critical_s

    def test_spec_roundtrip_and_diff(self):
        spec = ParallelSpec(data=4, fsdp=2,
                            collectives={"data": "lat"})
        assert spec.collectives == (("data", "lat"),)
        assert hash(spec) is not None
        back = spec_from_dict(
            {"data": 4, "fsdp": 2, "collectives": [["data", "lat"]]}
        )
        assert back.collectives == spec.collectives
        diff = spec_diff(ParallelSpec(data=4, fsdp=2), spec)
        assert "data-coll" in diff and "lat" in diff

    def test_invalid_strategy_rejected(self):
        with pytest.raises(ValueError):
            ParallelSpec(data=4, collectives={"data": "magic"})


def _token_loss(module, params, batch):
    return loss_fn(module.apply({"params": params}, batch), batch)


def _run_training(spec, grad_accum, steps=3):
    cfg = dataclasses.replace(GPTConfig.tiny(), dtype=jax.numpy.float32)
    model = GPT(cfg)
    opt = optax.adamw(1e-3)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
    )
    res = auto_accelerate(model, opt, tokens, _token_loss, spec=spec,
                          grad_accum=grad_accum)
    state = res.state
    batch = jax.device_put(tokens, res.batch_sharding)
    losses = []
    for _ in range(steps):
        state, m = res.train_step(state, batch)
        losses.append(float(m["loss"]))
    return losses


class TestOverlapBitIdentity:
    """Backward-overlap must be a scheduling change, not a numeric one."""

    @pytest.mark.parametrize(
        "spec",
        [ParallelSpec(data=8), ParallelSpec(data=4, fsdp=2)],
        ids=["dp-replicated-leaves", "dp-fsdp-sharded-leaves"],
    )
    def test_overlapped_matches_serialized_exactly(self, spec,
                                                   monkeypatch):
        overlapped = _run_training(spec, grad_accum=2)
        monkeypatch.setenv("DLROVER_TPU_COMMS_OVERLAP", "0")
        serialized = _run_training(spec, grad_accum=2)
        # Bit-identical, not merely close: on replicated leaves the
        # overlap hint splits the same reduction into buckets; on
        # sharded leaves it must stand down entirely.
        assert overlapped == serialized

    # Promoted to slow (~10s of XLA compiles): the fast-lane
    # parametrized case above already pins bit-identity for both leaf
    # classes; this arm only adds the lat-strategy spec variant.
    @pytest.mark.slow
    def test_lat_strategy_matches_too(self, monkeypatch):
        spec = ParallelSpec(data=2, fsdp=2)
        baseline = _run_training(spec, grad_accum=2)
        lat = _run_training(
            dataclasses.replace(spec, collectives={"data": "lat"}),
            grad_accum=2,
        )
        assert lat == baseline


class TestCommsGovernor:
    def test_defer_bounded_then_forced_through(self):
        log = EventLog()
        events_mod.install_sink(log.append)
        gov = CommsGovernor(client=None, max_defer_steps=2)
        gov.note_saturated(True)
        verdicts = [gov.allow_staging(step) for step in range(5)]
        assert verdicts == [False, False, True, False, False]
        defers = log.events(kinds=[EventKind.COMMS_DEFER])
        assert [e.args["streak"] for e in defers] == [1, 2, 1, 2]
        assert all(e.args["what"] == "staging" for e in defers)
        assert gov.stats()["defer_total"] == 4

    def test_unsaturated_always_allows_and_resets(self):
        gov = CommsGovernor(client=None, max_defer_steps=4)
        gov.note_saturated(True)
        assert not gov.allow_readback(1)
        gov.note_saturated(False)
        assert all(gov.allow_readback(s) for s in range(2, 6))
        assert gov.stats()["deferred_readback"] == 0

    def test_refresh_reads_kv_profile(self):
        kv = KVStoreService()
        gov = CommsGovernor(client=_KvClient(kv), refresh_s=0.0)
        assert gov.saturated() is False  # no profile yet → allow
        kv.set(LINK_PROFILE_KV_KEY,
               json.dumps({"fleet": {"saturated": True}}).encode())
        assert gov.saturated() is True
        kv.set(LINK_PROFILE_KV_KEY,
               json.dumps({"fleet": {"saturated": False}}).encode())
        assert gov.saturated() is False

    def test_engine_staging_defers_under_governor(self, tmp_path,
                                                  job_name):
        from dlrover_tpu.train.checkpoint import CheckpointEngine

        log = EventLog()
        events_mod.install_sink(log.append)
        gov = CommsGovernor(client=None, max_defer_steps=8)
        gov.note_saturated(True)
        install_governor(gov)
        engine = CheckpointEngine(str(tmp_path / "ckpts"))
        try:
            # Deferred before any D2H dispatch — same False as the
            # staging-pending skip, so callers need no new handling.
            assert engine.save_to_memory_async(7, {"x": 0}) is False
        finally:
            engine.close()
        [ev] = log.events(kinds=[EventKind.CKPT_IO])
        assert ev.args["op"] == "staging-defer"
        assert ev.args["step"] == 7 and ev.args["bytes"] == 0
        [defer] = log.events(kinds=[EventKind.COMMS_DEFER])
        assert defer.args["what"] == "staging"

    def test_staging_lands_only_outside_the_saturated_window(
        self, tmp_path, job_name
    ):
        """A save offered every step across a saturated window: no
        staging bytes land for a step inside it, each such offer is a
        ``staging-defer``, and the snapshots land again once it clears."""
        import jax.numpy as jnp

        from dlrover_tpu.train.checkpoint import CheckpointEngine

        log = EventLog()
        events_mod.install_sink(log.append)
        gov = CommsGovernor(client=None, max_defer_steps=8)
        install_governor(gov)
        state = {"w": jnp.arange(1 << 10, dtype=jnp.float32)}
        window = range(3, 6)
        engine = CheckpointEngine(str(tmp_path / "ckpts"))
        try:
            for step in range(1, 9):
                gov.note_saturated(step in window)
                landed = engine.save_to_memory_async(step, state)
                assert landed is (step not in window)
                if landed:
                    assert engine.wait_staged(timeout=30.0)
        finally:
            engine.close()
        io = log.events(kinds=[EventKind.CKPT_IO])
        staged = {e.args["step"] for e in io if e.args["op"] == "staging"}
        deferred = [e.args["step"] for e in io
                    if e.args["op"] == "staging-defer"]
        assert staged == set(range(1, 9)) - set(window)
        assert deferred == list(window)

    def test_chaos_degraded_probe_drives_deferral(self, monkeypatch):
        """End-to-end: injected link degrade → aggregator flags → kv
        profile → governor defers the hot-path I/O."""
        kv = KVStoreService()
        log = EventLog()
        events_mod.install_sink(log.append)
        events_mod.set_identity(0, "agent")
        agg = _agg(kv_store=kv)
        log.add_listener(agg.observe)
        probe = LinkProbe(interval=0, busy_fn=lambda: False,
                          sample_fn=lambda: dict(PROBE_OK))
        now = 0.0

        def rounds(n):
            nonlocal now
            for _ in range(n):
                now += 1.0
                probe.sample_once()  # through the armed chaos site
                agg.tick(now=now)

        rounds(4)
        gov = CommsGovernor(client=_KvClient(kv), refresh_s=0.0)
        assert gov.allow_staging(1)  # healthy fleet: nothing deferred
        _arm(monkeypatch, FaultPlan(seed=3, events=[
            FaultEvent(site="probe.link", kind="degrade", every=1,
                       args={"factor": 0.05}),
        ]))
        rounds(4)
        assert agg.saturated()
        assert not gov.allow_staging(2)
        assert not gov.allow_readback(2)
        [d1, d2] = log.events(kinds=[EventKind.COMMS_DEFER])
        assert {d1.args["what"], d2.args["what"]} == \
            {"staging", "readback"}
