"""Per-DC-pair asymmetric WANs + the sweep/campaign engine (ISSUE 6).

Covers the tentpole guarantees:

* **symmetric-default byte-identity** — a per-pair map holding one uniform
  profile (and the empty map) is bit-identical to the legacy two-class
  ``Netem`` across ``sync_cost`` (fluid + congestion + weighted branches,
  including the jitter RNG stream), ``step_time``,
  ``contended_transfer_time`` (the congestion-report arrays), and
  ``simulate_schedule``;
* **profile resolution** — ``netem.profile(u, v)`` precedence (per-link
  override > per-pair map > class default), asymmetry visible in RTT /
  roofline / sync costing, and ``normalize_wan_pairs`` validation;
* **``TopologySpec.wan_pairs`` JSON round-trip identity** — through an
  actual ``json.dumps``/``loads`` cycle, key normalization included;
* **sweep determinism** — the same sweep joined over 1 vs 2 process-pool
  workers is identical, ``random_campaign(seed)`` is a deterministic
  artifact of its seed, and dotted-field ``apply_overrides`` expansion
  validates co-dependent fields together.
"""

import dataclasses
import json

import numpy as np
import pytest

from repro.core.geo import GeoFabric, SyncOptions
from repro.core.wan import Netem, NetemProfile, PAPER_LAN, PAPER_WAN, normalize_wan_pairs
from repro.scenario import (
    Scenario,
    ScenarioEvent,
    Sweep,
    TopologySpec,
    WorkloadSpec,
    apply_overrides,
    fiber_latency_campaign,
    random_campaign,
    run_sweep,
)
from repro.scenario.sweep import overlap_benefit_curve

GRAD = 24_000_000


def _uniform_pairs(num_pods: int, profile: NetemProfile):
    return {
        (a, b): profile
        for a in range(1, num_pods + 1)
        for b in range(a + 1, num_pods + 1)
    }


class TestSymmetricByteIdentity:
    """A uniform per-pair map must be indistinguishable from the legacy
    two-class Netem — outputs *and* RNG stream."""

    @pytest.mark.parametrize("num_pods", [2, 3])
    def test_sync_cost_all_branches(self, num_pods):
        legacy = GeoFabric(num_pods, 2, seed=9)
        mapped = GeoFabric(
            num_pods, 2, seed=9, wan_pairs=_uniform_pairs(num_pods, PAPER_WAN)
        )
        for opts in (
            SyncOptions(),  # fluid + jitter: pins the RNG stream too
            SyncOptions(jitter=False),
            SyncOptions(jitter=False, congestion=True),
            SyncOptions(jitter=False, congestion=True, ecmp_weighted=True),
        ):
            for strategy in ("allreduce", "hier", "rs_ag_overlap"):
                a = legacy.sync_cost(strategy, GRAD, options=opts)
                b = mapped.sync_cost(strategy, GRAD, options=opts)
                assert a.wan_seconds == b.wan_seconds
                assert a.wan_bytes == b.wan_bytes
                assert a.bottleneck_link == b.bottleneck_link
                assert a.bottleneck_utilization == b.bottleneck_utilization
                assert [dataclasses.astuple(p) for p in a.phases] == [
                    dataclasses.astuple(p) for p in b.phases
                ]

    def test_step_time_and_jitter_stream(self):
        legacy = GeoFabric(2, 2, seed=3)
        mapped = GeoFabric(2, 2, seed=3, wan_pairs={(1, 2): PAPER_WAN})
        for _ in range(4):  # consecutive draws keep the streams aligned
            assert legacy.step_time(
                "allreduce", GRAD, 1.0, overlap_fraction=0.5
            ) == mapped.step_time("allreduce", GRAD, 1.0, overlap_fraction=0.5)

    def test_congestion_report_arrays(self):
        from repro.core.flows import ring_allreduce_flows

        legacy = GeoFabric(2, 2, seed=0)
        mapped = GeoFabric(2, 2, seed=0, wan_pairs={(2, 1): PAPER_WAN})
        flows = ring_allreduce_flows(legacy.workers(), GRAD, num_channels=4)
        a = legacy.timing.contended_transfer_time(flows)
        b = mapped.timing.contended_transfer_time(flows)
        np.testing.assert_array_equal(a.rates_gbps, b.rates_gbps)
        np.testing.assert_array_equal(a.completion_s, b.completion_s)
        np.testing.assert_array_equal(a.throughput_gbps, b.throughput_gbps)
        assert a.links == b.links

    def test_simulate_schedule(self):
        legacy = GeoFabric(2, 2, seed=0)
        mapped = GeoFabric(2, 2, seed=0, wan_pairs={(1, 2): PAPER_WAN})
        sched = legacy.build_schedule("rs_then_ag", GRAD)
        a = legacy.timing.contended_schedule_time(sched)
        b = mapped.timing.contended_schedule_time(sched)
        assert a.seconds == b.seconds
        np.testing.assert_array_equal(a.completion_s, b.completion_s)
        np.testing.assert_array_equal(a.peak_throughput_gbps, b.peak_throughput_gbps)

    def test_transfer_time_host_links_unified(self):
        geo = GeoFabric(2, 2, seed=0)
        host_link = ("d1h1", "d1l1")
        res = geo.timing.transfer_time({host_link: 10_000_000})
        lan_bw = geo.netem.lan.bandwidth_gbps
        assert res.seconds == 10_000_000 * 8.0 / (lan_bw * 1e9)

    def test_wan_roofline_identity_and_asymmetry(self):
        legacy = GeoFabric(3, 2, seed=0)
        mapped = GeoFabric(3, 2, seed=0, wan_pairs=_uniform_pairs(3, PAPER_WAN))
        assert legacy.wan_roofline_seconds(1e9, 8) == mapped.wan_roofline_seconds(1e9, 8)
        slow = GeoFabric(
            3, 2, seed=0,
            wan_pairs={(1, 2): NetemProfile(delay_ms=5.0, bandwidth_gbps=0.4)},
        )
        assert slow.wan_roofline_seconds(1e9, 8) > legacy.wan_roofline_seconds(1e9, 8)


class TestProfileResolution:
    def test_precedence_override_pair_class(self):
        geo = GeoFabric(2, 2, seed=0)
        pair_prof = NetemProfile(delay_ms=20.0, bandwidth_gbps=0.5)
        netem = Netem(
            geo.fabric, wan=PAPER_WAN, lan=PAPER_LAN, wan_pairs={(1, 2): pair_prof}
        )
        assert netem.profile("d1s1", "d2s2") == pair_prof
        assert netem.profile("d2s1", "d1s1") == pair_prof  # order-insensitive
        assert netem.profile("d1l1", "d1s1") == PAPER_LAN
        link_prof = NetemProfile(delay_ms=1.0, bandwidth_gbps=100.0)
        netem.override_link("d2s2", "d1s1", link_prof)
        assert netem.profile("d1s1", "d2s2") == link_prof
        assert netem.profile("d1s2", "d2s2") == pair_prof  # others keep the pair

    def test_unmapped_pair_falls_back_to_class_default(self):
        geo = GeoFabric(
            3, 2, seed=0,
            wan_pairs={(1, 2): NetemProfile(delay_ms=40.0, bandwidth_gbps=0.4)},
        )
        assert geo.netem.profile("d1s1", "d3s1") == PAPER_WAN
        r12 = geo.netem.base_rtt_ms("d1h1", "d2h1")
        r13 = geo.netem.base_rtt_ms("d1h1", "d3h1")
        assert r12 > r13  # the slow pair is visible end to end

    def test_asymmetry_moves_sync_cost(self):
        sym = GeoFabric(3, 2, seed=0)
        asym = GeoFabric(
            3, 2, seed=0,
            wan_pairs={(2, 3): NetemProfile(delay_ms=5.0, bandwidth_gbps=0.1)},
        )
        a = sym.sync_cost("allreduce", GRAD, jitter=False, congestion=True)
        b = asym.sync_cost("allreduce", GRAD, jitter=False, congestion=True)
        assert b.wan_seconds > a.wan_seconds

    def test_normalize_validation(self):
        with pytest.raises(ValueError, match="not a DC"):
            normalize_wan_pairs({(1, 1): PAPER_WAN})
        with pytest.raises(ValueError, match="same pair"):
            normalize_wan_pairs({(1, 2): PAPER_WAN, (2, 1): PAPER_LAN})
        with pytest.raises(ValueError, match="outside DCs"):
            normalize_wan_pairs({(1, 5): PAPER_WAN}, 3)
        with pytest.raises(TypeError):
            normalize_wan_pairs({(1, 2): "fast"})
        assert normalize_wan_pairs(None) == {}
        assert normalize_wan_pairs({(3, 1): PAPER_WAN}) == {(1, 3): PAPER_WAN}


class TestTopologySpecWanPairs:
    def test_json_round_trip_identity(self):
        spec = TopologySpec(
            num_pods=3,
            wan_pairs={
                (2, 1): NetemProfile(delay_ms=30.0, bandwidth_gbps=0.4),
                (1, 3): NetemProfile(delay_ms=4.0, bandwidth_gbps=2.0),
            },
        )
        restored = TopologySpec.from_dict(json.loads(json.dumps(spec.to_dict())))
        assert restored == spec
        # keys were normalized + sorted, so reversed input compares equal
        assert spec.wan_pairs[0][0] == (1, 2)

    def test_scenario_round_trip_with_wan_pairs(self):
        s = Scenario(
            name="asym",
            topology=TopologySpec(
                num_pods=2, wan_pairs={(1, 2): NetemProfile(delay_ms=12.0)}
            ),
            workload=WorkloadSpec(strategy="allreduce", grad_bytes=GRAD),
        )
        assert Scenario.from_dict(json.loads(json.dumps(s.to_dict()))) == s

    def test_legacy_dict_without_wan_pairs_loads(self):
        d = TopologySpec().to_dict()
        d.pop("wan_pairs")
        assert TopologySpec.from_dict(d) == TopologySpec()

    def test_build_threads_pairs_to_netem(self):
        prof = NetemProfile(delay_ms=25.0, bandwidth_gbps=0.6)
        geo = TopologySpec(num_pods=2, wan_pairs={(1, 2): prof}).build()
        assert geo.netem.profile("d1s1", "d2s1") == prof

    def test_pairs_validated_against_topology(self):
        with pytest.raises(ValueError, match="outside DCs"):
            TopologySpec(num_pods=2, wan_pairs={(1, 3): PAPER_WAN})


class TestApplyOverrides:
    def test_dotted_fields(self):
        base = Scenario(name="b", workload=WorkloadSpec(strategy="hier", grad_bytes=1))
        out = apply_overrides(
            base,
            {
                "name": "v",
                "workload.overlap_fraction": 0.5,
                "topology.wan.delay_ms": 9.0,
                "options.congestion": True,
                "events": (ScenarioEvent(kind="straggler", slowdown=2.0),),
            },
        )
        assert out.name == "v"
        assert out.workload.overlap_fraction == 0.5
        assert out.topology.wan.delay_ms == 9.0
        assert out.options.congestion is True
        assert out.events[0].kind == "straggler"
        assert base.workload.overlap_fraction == 0.0  # base untouched

    def test_codependent_fields_validate_together(self):
        base = Scenario(name="b")  # 2 pods
        out = apply_overrides(
            base,
            {
                "topology.wan_pairs": {(1, 3): NetemProfile(delay_ms=15.0)},
                "topology.num_pods": 3,
            },
        )
        assert out.topology.num_pods == 3
        assert out.topology.wan_pairs[0][0] == (1, 3)

    def test_bad_paths_raise(self):
        base = Scenario(name="b")
        with pytest.raises(ValueError, match="bad override field"):
            apply_overrides(base, {"workload.nope": 1})
        with pytest.raises(ValueError, match="no field"):
            apply_overrides(base, {"nope.deeper": 1})
        with pytest.raises(ValueError, match="non-spec field"):
            apply_overrides(base, {"name.x": 1})


class TestSweepEngine:
    def _small_sweep(self) -> Sweep:
        return fiber_latency_campaign(rtt_ms=(2.0, 40.0), overlap_fractions=(0.0, 0.75))

    def test_variant_expansion_and_names(self):
        sweep = self._small_sweep()
        variants = sweep.variants()
        assert [v.name for v in variants] == [
            "rtt2ms_f00", "rtt2ms_f75", "rtt40ms_f00", "rtt40ms_f75",
        ]
        assert variants[-1].topology.wan_pairs[0][1].delay_ms == 20.0

    def test_worker_count_never_changes_results(self):
        sweep = self._small_sweep()
        serial = run_sweep(sweep)
        parallel = run_sweep(sweep, workers=2)
        assert [r.to_dict() for r in serial.rows] == [
            r.to_dict() for r in parallel.rows
        ]

    def test_workers_are_spawned_pinned_to_the_cpu(self, monkeypatch):
        """Sweep workers see JAX_PLATFORMS=cpu from their first import and
        the parent's environment is left as it was."""
        import os

        from repro.scenario.sweep import _cpu_pool

        monkeypatch.setenv("JAX_PLATFORMS", "parent-value")
        with _cpu_pool(1) as pool:
            assert pool.submit(os.getenv, "JAX_PLATFORMS").result(timeout=120) == "cpu"
            assert pool._mp_context.get_start_method() == "spawn"
        assert os.environ["JAX_PLATFORMS"] == "parent-value"

    def test_benefit_curve_decays_with_rtt(self):
        curve = overlap_benefit_curve(run_sweep(self._small_sweep()))
        assert len(curve) == 2
        assert curve[1][1] < curve[0][1]

    def test_result_table_json_and_lookup(self):
        result = run_sweep(self._small_sweep())
        payload = json.loads(json.dumps(result.to_dict()))
        assert payload["campaign"] == "fiber_latency_campaign"
        assert len(payload["variants"]) == 4
        assert all("metrics" in v for v in payload["variants"])
        assert result.row("rtt2ms_f00").metrics["mean_step_seconds"] > 0
        assert len(result.metric("mean_step_seconds")) == 4

    def test_compare_gate_reads_campaign_table(self, tmp_path):
        from benchmarks.compare import compare

        result = run_sweep(self._small_sweep())
        for d in ("base", "new"):
            (tmp_path / d).mkdir()
            (tmp_path / d / "BENCH_campaign.json").write_text(
                json.dumps(result.to_dict())
            )
        _, regressions = compare(tmp_path / "base", tmp_path / "new")
        assert regressions == []

    def test_random_campaign_seed_determinism(self):
        a = random_campaign(seed=7, variants=3)
        b = random_campaign(seed=7, variants=3)
        assert a.overrides == b.overrides
        ra = run_sweep(a)
        rb = run_sweep(b, workers=2)
        assert [r.to_dict() for r in ra.rows] == [r.to_dict() for r in rb.rows]
        assert ra.seed == 7

    def test_degradation_axes_deterministic_and_worker_invariant(self):
        kw = dict(variants=4, degrade_probability=0.8, storm_probability=0.6)
        a = random_campaign(seed=21, **kw)
        b = random_campaign(seed=21, **kw)
        assert a.overrides == b.overrides
        kinds = {e.kind for ov in a.overrides for e in ov.get("events", ())}
        assert "degrade_pair" in kinds and "restore_degradation" in kinds
        assert "fail_switch" in kinds and "restore_switch" in kinds
        ra = run_sweep(a)
        rb = run_sweep(b, workers=2)
        assert [r.to_dict() for r in ra.rows] == [r.to_dict() for r in rb.rows]

    def test_degradation_axes_off_by_default_preserve_draw_stream(self):
        """Campaigns generated before the degradation/storm axes existed
        must replay byte-identically: probability 0 consumes no draws."""
        legacy = random_campaign(seed=6, variants=4)
        explicit = random_campaign(
            seed=6, variants=4, degrade_probability=0.0, storm_probability=0.0
        )
        assert legacy.overrides == explicit.overrides
        kinds = {e.kind for ov in legacy.overrides for e in ov.get("events", ())}
        assert kinds <= {"fail_link", "restore_link", "straggler"}

    def test_random_campaign_seeds_differ(self):
        a = random_campaign(seed=1, variants=3)
        b = random_campaign(seed=2, variants=3)
        assert a.overrides != b.overrides

    def test_random_campaign_specs_are_runnable_and_serializable(self):
        sweep = random_campaign(seed=3, variants=3)
        for v in sweep.variants():
            assert Scenario.from_dict(json.loads(json.dumps(v.to_dict()))) == v

    def test_serving_axis_deterministic_and_worker_invariant(self):
        """ISSUE 8 satellite: the ``serving_probability`` axis draws
        ServingSpecs deterministically and survives the process pool."""
        kw = dict(variants=3, serving_probability=1.0)
        a = random_campaign(seed=42, **kw)
        b = random_campaign(seed=42, **kw)
        assert a.overrides == b.overrides
        assert all("serving" in ov for ov in a.overrides)
        for v in a.variants():
            assert v.serving is not None
            assert Scenario.from_dict(json.loads(json.dumps(v.to_dict()))) == v
        ra = run_sweep(a)
        rb = run_sweep(b, workers=2)
        assert [r.to_dict() for r in ra.rows] == [r.to_dict() for r in rb.rows]
        assert all("serving_p99_ms" in r.metrics for r in ra.rows)

    def test_serving_axis_off_by_default_preserves_draw_stream(self):
        """Campaigns generated before the serving axis existed must
        replay byte-identically: probability 0 consumes no draws."""
        legacy = random_campaign(seed=6, variants=4)
        explicit = random_campaign(seed=6, variants=4, serving_probability=0.0)
        assert legacy.overrides == explicit.overrides
        assert all("serving" not in ov for ov in legacy.overrides)
