"""Scenario harness: the shared workload model (benchmarks/workload.py),
the pure gate math (fairness, flood attribution), and the artifact schema
from a miniature end-to-end run of benchmarks/scenario_bench.py."""

import json

import numpy as np
import pytest

from benchmarks.scenario_bench import (
    ScenarioConfig,
    fairness_check,
    flood_attribution,
    run_scenario,
)
from benchmarks.workload import (
    Phase,
    TenantSpec,
    WorkloadModel,
    demand_totals,
    shape_multiplier,
    zipf_flow_sequence,
)


class TestWorkloadModel:
    def test_zipf_stream_is_bounded_and_deterministic(self):
        a = zipf_flow_sequence(64, 1.1, 10_000, seed=3)
        b = zipf_flow_sequence(64, 1.1, 10_000, seed=3)
        assert np.array_equal(a, b)
        assert a.min() >= 0 and a.max() < 64
        # Zipfian, not uniform: rank 1 dominates
        counts = np.bincount(a, minlength=64)
        assert counts[0] > 4 * counts[32]

    def test_tenant_stream_lands_in_its_flow_range(self):
        t = TenantSpec("x", first_flow=100, n_flows=50, share=0.5,
                       base_rate=100.0)
        s = t.flow_stream(5000, seed=9)
        assert s.min() >= 100 and s.max() < 150

    def test_tenant_seed_salt_is_stable_not_hash(self):
        # crc32 salting: the same (tenant, seed) gives the same stream in
        # every process (hash() is per-process randomized)
        t = TenantSpec("x", 0, 8, share=0.5, base_rate=100.0)
        assert t.flow_stream(5, seed=1).tolist() == t.flow_stream(
            5, seed=1).tolist()
        u = TenantSpec("y", 0, 8, share=0.5, base_rate=100.0)
        assert t.flow_stream(50, seed=1).tolist() != u.flow_stream(
            50, seed=1).tolist()

    def test_shape_multipliers(self):
        assert shape_multiplier("steady", 5.0, 0.5) == 1.0
        assert shape_multiplier("ramp", 2.0, 1.0) == 2.0
        assert shape_multiplier("ramp", 2.0, 0.0) == pytest.approx(0.1)
        assert shape_multiplier("spike", 8.0, 0.5) == 8.0
        assert shape_multiplier("spike", 8.0, 0.1) == 1.0
        assert shape_multiplier("flashcrowd", 4.0, 0.1) == 1.0
        assert shape_multiplier("flashcrowd", 4.0, 0.99) == pytest.approx(
            4.0, rel=0.01)
        assert shape_multiplier("diurnal", 3.0, 0.5) == pytest.approx(3.0)
        assert shape_multiplier("diurnal", 3.0, 0.0) == pytest.approx(1.0)

    def test_spike_shape_scopes_to_shape_tenants(self):
        ph = Phase("p", 1.0, "spike", magnitude=6.0, shape_tenants=["a"])
        assert ph.multiplier("a", 0.5) == 6.0
        assert ph.multiplier("b", 0.5) == 1.0

    def test_send_schedule_integrates_the_rate(self):
        t = TenantSpec("x", 0, 8, share=0.5, base_rate=1000.0, batch=10)
        model = WorkloadModel([t], [Phase("p", 2.0, "steady")], seed=1)
        sched = model.send_schedule(model.phases[0], t)
        # 1000 rows/s x 2s / 10 rows per frame = ~200 frames
        assert abs(sched.size - 200) <= 2
        assert sched.min() >= 0.0 and sched.max() < 2.0
        assert np.all(np.diff(sched) >= 0)  # absolute, monotone offsets

    def test_demand_totals(self):
        t = TenantSpec("x", 0, 8, share=0.5, base_rate=500.0, batch=5)
        model = WorkloadModel([t], [Phase("p", 1.0, "steady")], seed=1)
        d = demand_totals(model, model.phases[0])
        assert d["x"] == pytest.approx(500.0, rel=0.05)


class TestFairnessMath:
    SHARES = {"a": 0.4, "b": 0.4}

    def test_no_starvation_passes(self):
        sums = {"a": {"pass": 400, "block": 0, "shed": 0, "other": 0},
                "b": {"pass": 395, "block": 5, "shed": 100, "other": 0}}
        res = fairness_check(sums, self.SHARES,
                             {"a": 500, "b": 500}, tolerance=0.1)
        assert res["ok"] and not any(
            t["starved"] for t in res["tenants"].values())

    def test_starved_tenant_fails(self):
        # b demanded plenty but was served far below 40% of the total
        sums = {"a": {"pass": 900, "block": 0, "shed": 0, "other": 0},
                "b": {"pass": 100, "block": 0, "shed": 800, "other": 0}}
        res = fairness_check(sums, self.SHARES,
                             {"a": 1000, "b": 1000}, tolerance=0.1)
        assert not res["ok"]
        assert res["tenants"]["b"]["starved"]
        assert not res["tenants"]["a"]["starved"]

    def test_low_demand_is_not_starvation(self):
        # b got little because it ASKED for little
        sums = {"a": {"pass": 900, "block": 0, "shed": 0, "other": 0},
                "b": {"pass": 100, "block": 0, "shed": 0, "other": 0}}
        res = fairness_check(sums, self.SHARES,
                             {"a": 1000, "b": 100}, tolerance=0.1)
        assert res["ok"]

    def test_blocks_count_as_served(self):
        # a BLOCKED verdict is an answer (the rule said no); only sheds
        # deny service
        sums = {"a": {"pass": 0, "block": 400, "shed": 0, "other": 0},
                "b": {"pass": 400, "block": 0, "shed": 0, "other": 0}}
        res = fairness_check(sums, self.SHARES,
                             {"a": 500, "b": 500}, tolerance=0.1)
        assert res["ok"]

    def test_excluded_tenants_stay_out_of_the_math(self):
        sums = {"a": {"pass": 100, "block": 0, "shed": 0, "other": 0},
                "lease": {"pass": 9000, "block": 0, "shed": 0, "other": 0}}
        res = fairness_check(sums, {"a": 0.9, "lease": 0.0},
                             {"a": 100}, tolerance=0.1,
                             exclude={"lease"})
        assert res["ok"] and "lease" not in res["tenants"]
        assert res["totalServed"] == 100


class TestFloodAttribution:
    def test_names_the_largest_arrival_increase(self):
        base = {"a": {"pass": 100, "block": 0, "shed": 0},
                "b": {"pass": 100, "block": 0, "shed": 0}}
        flood = {"a": {"pass": 120, "block": 0, "shed": 0},
                 "b": {"pass": 150, "block": 50, "shed": 700}}
        assert flood_attribution(base, flood, 1.0, 1.0) == "b"

    def test_sheds_count_as_arrivals(self):
        # the flooder's excess got shed: served-only accounting would
        # name the wrong tenant
        base = {"a": {"pass": 100, "block": 0, "shed": 0},
                "b": {"pass": 100, "block": 0, "shed": 0}}
        flood = {"a": {"pass": 200, "block": 0, "shed": 0},
                 "b": {"pass": 100, "block": 0, "shed": 900}}
        assert flood_attribution(base, flood, 1.0, 1.0) == "b"

    def test_exclude(self):
        base = {"a": {"pass": 1, "block": 0, "shed": 0}}
        flood = {"a": {"pass": 2, "block": 0, "shed": 0},
                 "x": {"pass": 999, "block": 0, "shed": 0}}
        assert flood_attribution(base, flood, 1.0, 1.0,
                                 exclude={"x"}) == "a"


class TestScenarioArtifact:
    @pytest.fixture(scope="class")
    def doc(self, tmp_path_factory):
        tenants = [
            TenantSpec("t-a", 0, 16, share=0.3, base_rate=400.0, batch=8),
            TenantSpec("t-b", 16, 16, share=0.3, base_rate=400.0, batch=8),
        ]
        phases = [
            Phase("warmup", 0.8, "steady", measured=False),
            Phase("steady", 1.0, "steady"),
            Phase("spike", 1.2, "spike", magnitude=4.0,
                  shape_tenants=["t-a"]),
        ]
        model = WorkloadModel(tenants, phases, seed=13)
        cfg = ScenarioConfig(
            name="mini", model=model, flood_tenant="t-a",
            burn_gates={"t-a": 100.0, "t-b": 100.0},
            out_dir=str(tmp_path_factory.mktemp("scenario")),
            publish_round=False,
        )
        return run_scenario(cfg)

    def test_schema_and_shape(self, doc):
        assert doc["schema"] == "sentinel-scenario/1"
        assert doc["seed"] == 13
        assert [p["name"] for p in doc["phases"]] == [
            "warmup", "steady", "spike"]
        assert {t["name"] for t in doc["tenants"]} == {"t-a", "t-b"}
        assert set(doc["gates"]) == {
            "p99Burn", "fairness", "overAdmission", "clientErrors",
            "floodAttribution", "degradeAttribution",
            "timelineReconciles"}

    def test_artifact_is_json_serializable(self, doc):
        json.dumps(doc)

    def test_timeline_reconciliation_holds(self, doc):
        # the invariant that must hold on ANY run, loaded or idle
        assert doc["gates"]["timelineReconciles"]["ok"], (
            doc["gates"]["timelineReconciles"]["diffs"])

    def test_drivers_delivered_and_were_answered(self, doc):
        for ph in doc["phases"]:
            for name in ("t-a", "t-b"):
                st = ph["tenants"][name]["driver"]
                assert st["sent_rows"] > 0
                assert st["errors"] == 0
        # per-second series exist for measured phases
        spike = doc["phases"][2]
        assert any(spike["tenants"][n]["series"] for n in ("t-a", "t-b"))

    def test_phases_carry_wall_bounds(self, doc):
        for prev, cur in zip(doc["phases"], doc["phases"][1:]):
            assert prev["beginMs"] < prev["endMs"] <= cur["beginMs"] + 1000

    def test_the_run_leaves_the_process_its_default_objective(self, doc):
        """``run_scenario`` sets the p99 objective for its own SLO plane;
        left set, every later SLO plane of the process (the next test file
        on this worker) would read the scenario's 150 ms for the default."""
        from sentinel_tpu.core.config import SentinelConfig
        from sentinel_tpu.trace.slo import KEY_OBJECTIVE_MS

        assert doc["objectiveMs"] == 150.0
        assert SentinelConfig.get(KEY_OBJECTIVE_MS) is None
