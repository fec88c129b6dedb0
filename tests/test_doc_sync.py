"""docs/OBSERVABILITY.md ↔ code sync.

The observability doc is the series reference operators build dashboards
from; a series it documents must exist in code, and a series the exporter
actually emits must be documented. Same contract for the
``clusterServerStats`` key table. These tests are pure string checks — no
server, no sockets — so drift fails fast in tier-1.
"""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(REPO, "docs", "OBSERVABILITY.md")
SRC = os.path.join(REPO, "sentinel_tpu")


def _doc_text():
    with open(DOC) as f:
        return f.read()


def _source_corpus():
    chunks = []
    for root, _dirs, files in os.walk(SRC):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    chunks.append(f.read())
    return "\n".join(chunks)


def _doc_series():
    """Backticked `sentinel_*` tokens in the doc (concrete names only —
    globs like `sentinel_server_*` document families, not series)."""
    names = set(re.findall(r"`(sentinel_[a-z0-9_]+)`", _doc_text()))
    return {n for n in names if not n.endswith("_")}


def _rendered_series():
    """Series names the exporter actually emits, with representative
    state seeded so the traffic-gated sections light up."""
    from sentinel_tpu.metrics.exporter import render
    from sentinel_tpu.metrics.server import reset_server_metrics_for_tests
    from sentinel_tpu.trace.slo import (
        reset_slo_plane_for_tests,
        slo_plane,
    )

    reset_server_metrics_for_tests()
    reset_slo_plane_for_tests()
    try:
        plane = slo_plane()
        plane.record("doc-sync", 5.0, n=4)
        plane.record_shed("doc-sync", "overload", n=1)
        text = render()
    finally:
        reset_server_metrics_for_tests()
        reset_slo_plane_for_tests()
    names = set()
    for line in text.splitlines():
        m = re.match(r"# TYPE (sentinel_[a-z0-9_]+) ", line)
        if m:
            names.add(m.group(1))
            continue
        m = re.match(r"(sentinel_[a-z0-9_]+)[{ ]", line)
        if m:
            base = m.group(1)
            base = re.sub(r"_(bucket|sum|count)$", "", base)
            names.add(base)
    return names


class TestSeriesSync:
    def test_every_documented_series_exists_in_code(self):
        corpus = _source_corpus()
        missing = []
        for name in sorted(_doc_series()):
            # composed names (sentinel_server_shard_pulls_total) are built
            # from a prefix + a short literal at the render site
            short = name.replace("sentinel_server_", "").replace(
                "sentinel_", "")
            if name not in corpus and f'"{short}"' not in corpus and \
                    f"'{short}'" not in corpus:
                missing.append(name)
        assert not missing, (
            f"documented in OBSERVABILITY.md but absent from code: {missing}"
        )

    def test_every_rendered_series_is_documented(self):
        doc = _doc_text()
        documented = _doc_series()
        undocumented = []
        for name in sorted(_rendered_series()):
            if name not in documented and name not in doc:
                undocumented.append(name)
        assert not undocumented, (
            f"rendered by the exporter but not in OBSERVABILITY.md: "
            f"{undocumented}"
        )


class TestClusterServerStatsSync:
    def _doc_keys(self):
        """Keys listed in the doc's clusterServerStats table."""
        text = _doc_text()
        start = text.index("## The `clusterServerStats` command")
        end = text.index("\n## ", start + 1)
        section = text[start:end]
        keys = set()
        for row in re.findall(r"^\| (`[^|]+`(?: / `[^|]+`)*) \|", section,
                              re.M):
            keys.update(re.findall(r"`([A-Za-z]+)`", row))
        assert keys, "clusterServerStats key table not found in the doc"
        return keys

    def _live_keys(self):
        import sentinel_tpu.transport.handlers as handlers

        out = handlers.cmd_cluster_server_stats({}, "")
        assert isinstance(out, dict)
        json.dumps(out)  # the command surface must stay JSON-serializable
        return set(out)

    def test_every_stats_key_is_documented(self):
        missing = self._live_keys() - self._doc_keys()
        assert not missing, (
            f"clusterServerStats keys missing from OBSERVABILITY.md's "
            f"table: {sorted(missing)}"
        )

    def test_every_documented_key_exists(self):
        stale = self._doc_keys() - self._live_keys()
        assert not stale, (
            f"OBSERVABILITY.md documents clusterServerStats keys the "
            f"command no longer returns: {sorted(stale)}"
        )


class TestDocCrossLinks:
    def test_readme_links_the_doc(self):
        with open(os.path.join(REPO, "README.md")) as f:
            readme = f.read()
        assert "docs/OBSERVABILITY.md" in readme
        assert "trace/" in readme

    @pytest.mark.parametrize("needle", [
        "sentinel-trace-spans/1",
        "sentinel-blackbox/1",
        "cluster/server/trace",
        "cluster/server/slo",
        "cluster/server/metric",
        "SENTINEL_TRACE",
        "SENTINEL_BLACKBOX_DIR",
        "SENTINEL_TIMELINE_DIR",
        "burn = over_fraction / 0.01",
    ])
    def test_doc_covers_trace_surface(self, needle):
        assert needle in _doc_text()

    @pytest.mark.parametrize("needle", [
        # the wire op and its validation taxonomy
        "OUTCOME_REPORT",
        "`sentinel_outcome_dropped_total`",
        "`unknown_flow`",
        # the device columns and their reads
        "`sentinel_flow_rt_p99_ms`",
        "`sentinel_flow_exception_qps`",
        # the RT-objective half of the SLO plane
        "sentinel.tpu.slo.rt.p99.ms",
        "`sentinel_slo_rt_burn_rate`",
        # rotating-log search surface
        "search_stat_log",
        # the reconciliation gate and its runners
        "tests/test_outcome.py",
        "examples/outcome_demo.py",
        "`outcome-smoke`",
    ])
    def test_doc_covers_outcome_surface(self, needle):
        assert needle in _doc_text()


class TestShapingDocSync:
    """docs/SHAPING.md ↔ kernel sync: the doc carries the queue-cap math
    (rules.py defers to it) and names the verification surface."""

    def _text(self):
        with open(os.path.join(REPO, "docs", "SHAPING.md")) as f:
            return f.read()

    def test_readme_links_the_doc(self):
        with open(os.path.join(REPO, "README.md")) as f:
            readme = f.read()
        assert "docs/SHAPING.md" in readme
        assert "shaping_drill.py" in readme

    @pytest.mark.parametrize("needle", [
        # the clamp rules.py promises the doc carries
        "(n_buckets - 1) * bucket_ms",
        # the columns and clocks
        "warning_token",
        "max_queue_ms",
        "latestPassedTime",
        # the cross-batch charge and its mechanism
        "add_future",
        # client + lease surfaces
        "wait_and_admit",
        "NOT_LEASABLE",
        # HA: the relative MOVE keys and the replication keys
        "shaping_lpt_rel",
        "shaping_lpt",
        # verification surface
        "sentinel-shaping-drill/1",
        "tests/test_shaping.py",
        "benchmarks/shaping_drill.py",
    ])
    def test_doc_names_the_surface(self, needle):
        assert needle in self._text()

    def test_doc_queue_cap_matches_the_kernel(self):
        """The 900ms default-cap number in the doc is derived from config
        defaults — keep them in sync."""
        from sentinel_tpu.engine import EngineConfig

        cfg = EngineConfig()
        cap = (cfg.n_buckets - 1) * cfg.bucket_ms
        assert f"**{cap} ms**" in self._text()


class TestScenarioDocSync:
    """docs/SCENARIOS.md ↔ harness sync: the doc names the gates and the
    schema the artifact actually carries."""

    def _text(self):
        with open(os.path.join(REPO, "docs", "SCENARIOS.md")) as f:
            return f.read()

    def test_readme_links_the_doc(self):
        with open(os.path.join(REPO, "README.md")) as f:
            readme = f.read()
        assert "docs/SCENARIOS.md" in readme
        assert "scenario_bench.py" in readme

    @pytest.mark.parametrize("needle", [
        "sentinel-scenario/1",
        "benchmarks/workload.py",
        "cluster/server/metric",
        "zipf_flow_sequence",
        "send_schedule",
        "--smoke",
    ])
    def test_doc_names_the_surface(self, needle):
        assert needle in self._text()

    def test_doc_lists_every_gate(self):
        from benchmarks.scenario_bench import smoke_config

        text = self._text()
        for gate in ("p99Burn", "fairness", "overAdmission",
                     "clientErrors", "floodAttribution",
                     "timelineReconciles"):
            assert f"`{gate}`" in text
        # the smoke profile the doc promises is the one CI runs
        cfg = smoke_config()
        assert cfg.door == "tcp" and cfg.replica is False
        assert any(p.chaos for p in cfg.model.phases)


class TestDegradeDocSync:
    """docs/DEGRADE.md ↔ breaker plane sync: the doc names the strategy
    math, the tensor columns, the HA keys, and the verification surface —
    each of which exists in code, and the derived numbers it quotes come
    from the live dtypes/enums, not a stale copy."""

    def _text(self):
        with open(os.path.join(REPO, "docs", "DEGRADE.md")) as f:
            return f.read()

    def test_cross_links(self):
        with open(os.path.join(REPO, "README.md")) as f:
            readme = f.read()
        assert "docs/DEGRADE.md" in readme
        assert "degrade_drill.py" in readme
        for doc in ("ROBUSTNESS.md", "CLUSTER_HA.md", "OBSERVABILITY.md"):
            with open(os.path.join(REPO, "docs", doc)) as f:
                assert "DEGRADE.md" in f.read(), f"{doc} lost the link"

    @pytest.mark.parametrize("needle", [
        # the three strategies and their knobs
        "SLOW_REQUEST_RATIO",
        "ERROR_RATIO",
        "ERROR_COUNT",
        "min_request_amount",
        "stat_interval_ms",
        "recovery_timeout_ms",
        "slow_rt_ms",
        # the state columns and the probe ticket
        "`opened_ms`",
        "`probe_ms`",
        "HALF_OPEN",
        # what feeds them and what they answer
        "OUTCOME_REPORT",
        "`DEGRADED`",
        "NOT_LEASABLE",
        # HA: replication delta keys and the relative MOVE keys
        "breaker_fids",
        "breaker_state",
        "breaker_opened_rel",
        # the metric surface and its host-scan caveat
        "`sentinel_breaker_transitions_total`",
        "`sentinel_breaker_state`",
        "net edges",
        # verification surface
        "sentinel-degrade-drill/1",
        "tests/test_degrade.py",
        "benchmarks/degrade_drill.py",
        "--degraded",
        "`degrade-smoke`",
    ])
    def test_doc_names_the_surface(self, needle):
        assert needle in self._text()

    def test_doc_numbers_come_from_code(self):
        """The per-flow byte cost and the DEGRADED wire code the doc quotes
        are derived from the live columns, not hand-copied."""
        import numpy as np

        from sentinel_tpu.engine import EngineConfig, TokenStatus, make_state

        state = make_state(EngineConfig(max_flows=8, max_namespaces=2,
                                        batch_size=16))
        per_flow = sum(
            np.asarray(leaf).dtype.itemsize for leaf in state.breaker
        )
        text = self._text()
        assert f"**{per_flow} bytes**" in text
        assert f"status code **{int(TokenStatus.DEGRADED)}**" in text


class TestPushDocSync:
    """Push-plane docs ↔ code sync: CLUSTER_HA.md's push-plane + election
    sections, ROBUSTNESS.md's push-on/push-dark staleness table, and the
    OBSERVABILITY.md rows all name surfaces that exist in code."""

    def _ha(self):
        with open(os.path.join(REPO, "docs", "CLUSTER_HA.md")) as f:
            return f.read()

    def _rob(self):
        with open(os.path.join(REPO, "docs", "ROBUSTNESS.md")) as f:
            return f.read()

    @pytest.mark.parametrize("needle", [
        # the five frame types and the delivery contract
        "## Push plane (wire rev 7)",
        "LEASE_REVOKE",
        "BREAKER_FLIP",
        "RULE_EPOCH_INVALIDATE",
        "SHARD_MAP_PUSH",
        "BROWNOUT_ADVISORY",
        "at-most-once",
        "push=False",
        # the election: the lock, its arbiter, and its class
        "CoordinatorElection",
        "coordinator_lock",
        "lock_ttl_ms",
        "claim_lost",
        # verification surface
        "--only-push",
        "`push-smoke`",
    ])
    def test_cluster_ha_names_the_surface(self, needle):
        assert needle in self._ha()

    @pytest.mark.parametrize("needle", [
        "## Staleness bounds: push-on vs push-dark",
        "max(10×RTT, 25ms)",
        "`push=False`",
        "`LEASE_REVOKE`",
        "`BROWNOUT_ADVISORY`",
    ])
    def test_robustness_carries_the_bound_table(self, needle):
        assert needle in self._rob()

    @pytest.mark.parametrize("needle", [
        "sentinel_push_frames_total",
        "`sentinel_push_revocations_total`",
        "`sentinel_push_staleness_ms`",
        "`sentinel_client_unknown_frames_total`",
    ])
    def test_observability_documents_the_series(self, needle):
        assert needle in _doc_text()

    def test_doc_frame_labels_match_the_wire(self):
        """The per-type labels OBSERVABILITY.md enumerates are the hub's
        live PUSH_TYPE_NAMES, not a stale copy."""
        from sentinel_tpu.cluster.push import PUSH_TYPE_NAMES

        text = _doc_text()
        for label in PUSH_TYPE_NAMES.values():
            assert f"`{label}`" in text, f"push type {label} undocumented"

    def test_cross_links(self):
        assert "#staleness-bounds-push-on-vs-push-dark" in self._ha()
        assert "#push-plane-wire-rev-7" in self._rob()
        assert "#push-plane-wire-rev-7" in _doc_text()


class TestPerfRecordSync:
    """The root PERF.md ↔ program sync. §3 of the record names, per layer,
    the always-on histogram or counter each per-layer metric of the ledger is
    read from, and the program names the device-time metrics filter on. The
    harness reads them out of ``ServerMetrics.stage_snapshot()`` and out of
    the trace by those names, so a rename turns a metric ``null`` in the
    ledger; it fails here first."""

    def _text(self):
        with open(os.path.join(REPO, "PERF.md")) as f:
            return f.read()

    @pytest.mark.parametrize("name", [
        # the eight phases inside one dispatch (PR 24)
        "permit_wait_ms",
        "prep_ms",
        "lock_wait_ms",
        "launch_ms",
        "reply_queue_wait_ms",
        "device_wait_ms",
        "fetch_ms",
        "account_ms",
        # one packed verdict buffer per dispatch (PR 25)
        "verdict_copy_ready_total",
        "verdict_host_reads_total",
        # the param lane (PR 27)
        "param_values_total",
        "param_dispatch_total",
        "param_blocked_total",
        "param_requests_total",
        # the decide step's arms (PR 31)
        "decide_dispatch_total",
        "decide_rows_total",
        "decide_all_arms_live_total",
        "decide_shaped_rows_total",
        # the door's two halves and the native lane's queue wait (PR 38)
        "door_in_ms",
        "door_wake_ms",
        "door_out_ms",
        "door_residence_ms",
        "queue_wait_ms",
        # the control thread's wake-ups (PR 51)
        "control_wakeups_total",
        "control_idle_wakeups_total",
    ])
    def test_record_names_what_the_program_snapshots(self, name):
        from sentinel_tpu.metrics.server import ServerMetrics

        metrics = ServerMetrics()
        assert f"`{name}`" in self._text()
        assert name in metrics.stage_snapshot()
        if name.endswith("_ms"):  # a histogram: the command surface has it too
            assert name in metrics.snapshot()["stages"]

    def test_record_program_names_are_what_the_steps_jit_under(self):
        """``jit_decide*``, ``jit_param_decide*`` and ``jit_outcome_step`` in
        §3 are ``jit_`` + the names the steps are built under."""
        from sentinel_tpu.engine.config import EngineConfig
        from sentinel_tpu.engine.decide import (
            decide_donating,
            decide_fused_donating,
            step_name,
        )
        from sentinel_tpu.engine.outcome import outcome_step_donating
        from sentinel_tpu.engine.param import ParamConfig, make_param_step

        text = self._text()
        for prefix in ("jit_decide", "jit_param_decide", "jit_outcome_step"):
            assert f"`{prefix}" in text
        cfg = EngineConfig(max_flows=16, max_namespaces=4, batch_size=64)
        assert step_name("decide", cfg, True) == "decide_b64_uniform"
        assert step_name("decide_sharded_fused", cfg, False, 4) == (
            "decide_sharded_fused_d4_b64_mixed"
        )
        programs = [
            decide_donating(cfg, grouped=True, uniform=True),
            decide_fused_donating(cfg, 2, grouped=True),
        ]
        for step in programs:
            assert f"jit_{step.__name__}".startswith("jit_decide")
        pcfg = ParamConfig(max_param_rules=4, width=128)
        param_step = make_param_step(pcfg, 64, "jax")
        assert f"jit_{param_step.__name__}" == "jit_param_decide_b64"
        assert f"jit_{outcome_step_donating(cfg).__name__}" == (
            "jit_outcome_step"
        )


class TestStateColumnSync:
    """The state's pytrees ↔ the table of state columns. Snapshot, delta,
    MOVE blob, the clock's re-base and the mesh placement are loops over
    the table (``cluster/state_codec.py``), so a leaf that ``EngineState``
    or ``ParamState`` gains without an entry would ride none of them: it
    fails here and not in a failover."""

    def test_the_table_lists_the_leaves_the_pytrees_declare(self):
        import typing

        from sentinel_tpu.engine.param import PARAM_COLUMNS, ParamState
        from sentinel_tpu.engine.state import STATE_COLUMNS, EngineState

        declared = [
            f"{family}.{field}"
            for family, leaves in typing.get_type_hints(EngineState).items()
            for field in leaves._fields
        ] + [f"param.{field}" for field in ParamState._fields]
        listed = [c.name for c in STATE_COLUMNS + PARAM_COLUMNS]
        assert sorted(listed) == sorted(declared)
        assert len(set(listed)) == len(listed)
        assert {c.family for c in STATE_COLUMNS} == set(EngineState._fields)
        # every window's counts are bucketed by a starts of its own family
        for c in STATE_COLUMNS + PARAM_COLUMNS:
            if c.kind == "window":
                assert f"{c.family}.starts" in listed
