"""Per-namespace per-second metric timeline (metrics/timeline.py): ring
bucketing, file rotation + round-trip, the memory/file merged query, the
``cluster/server/metric`` command, and the counter↔timeline reconciliation
invariant the scenario harness gates on."""

import numpy as np
import pytest

from sentinel_tpu.metrics.server import (
    reset_server_metrics_for_tests,
    server_metrics,
)
from sentinel_tpu.metrics.timeline import (
    MetricTimeline,
    TimelineSample,
    TimelineSearcher,
    TimelineWriter,
    configure_timeline,
    reset_timeline_for_tests,
    timeline,
)

T0 = 1_754_000_000  # an arbitrary fixed wall second


@pytest.fixture(autouse=True)
def fresh():
    reset_server_metrics_for_tests()
    yield
    reset_server_metrics_for_tests()


class TestRingBucketing:
    def test_same_second_accumulates(self):
        tl = MetricTimeline(window_s=60)
        tl.record("a", n_pass=3, now_s=T0)
        tl.record("a", n_pass=2, n_block=1, now_s=T0)
        (s,) = tl.query(T0 * 1000, T0 * 1000)
        assert (s.passed, s.blocked, s.shed, s.other) == (5, 1, 0, 0)
        assert s.timestamp_ms == T0 * 1000

    def test_seconds_are_distinct_points(self):
        tl = MetricTimeline(window_s=60)
        tl.record("a", n_pass=1, now_s=T0)
        tl.record("a", n_pass=10, now_s=T0 + 1)
        out = tl.query(T0 * 1000, (T0 + 1) * 1000)
        assert [(s.timestamp_ms // 1000, s.passed) for s in out] == [
            (T0, 1), (T0 + 1, 10)]

    def test_namespaces_are_independent(self):
        tl = MetricTimeline(window_s=60)
        tl.record("a", n_pass=4, now_s=T0)
        tl.record("b", n_shed=7, now_s=T0)
        by_ns = {s.namespace: s for s in tl.query(0, T0 * 1000)}
        assert by_ns["a"].passed == 4 and by_ns["a"].shed == 0
        assert by_ns["b"].shed == 7 and by_ns["b"].passed == 0
        assert tl.namespaces() == ["a", "b"]

    def test_stale_slot_is_lazily_zeroed(self):
        # the ring reuses slot (sec % window); a write one full window
        # later must not inherit the old second's counts
        tl = MetricTimeline(window_s=10)
        tl.record("a", n_pass=100, now_s=T0)
        tl.record("a", n_pass=1, now_s=T0 + 10)  # same slot index
        assert tl.query(T0 * 1000, T0 * 1000) == []  # old second is gone
        (s,) = tl.query((T0 + 10) * 1000, (T0 + 10) * 1000)
        assert s.passed == 1

    def test_p99_is_conservative_bucket_edge(self):
        tl = MetricTimeline(window_s=60)
        tl.record("a", n_pass=10, latency_ms=1.5, now_s=T0)
        (s,) = tl.query(T0 * 1000, T0 * 1000)
        # geometric edges: the reported p99 is the smallest edge >= the
        # recorded latency (never an underestimate)
        assert s.p99_ms >= 1.5
        assert s.p99_ms < 1.5 * 1.6  # within one bucket ratio
        assert s.max_ms == 1.5

    def test_shed_rows_carry_no_latency(self):
        tl = MetricTimeline(window_s=60)
        tl.record("a", n_shed=5, now_s=T0)
        (s,) = tl.query(T0 * 1000, T0 * 1000)
        assert s.shed == 5 and s.p99_ms is None and s.max_ms is None

    def test_query_window_filters(self):
        tl = MetricTimeline(window_s=60)
        for d in range(5):
            tl.record("a", n_pass=1, now_s=T0 + d)
        mid = tl.query((T0 + 1) * 1000, (T0 + 3) * 1000)
        assert [s.timestamp_ms // 1000 for s in mid] == [
            T0 + 1, T0 + 2, T0 + 3]
        assert tl.query((T0 + 9) * 1000, (T0 + 9) * 1000) == []

    def test_query_namespace_filter(self):
        tl = MetricTimeline(window_s=60)
        tl.record("a", n_pass=1, now_s=T0)
        tl.record("b", n_pass=1, now_s=T0)
        out = tl.query(0, T0 * 1000, namespace="b")
        assert [s.namespace for s in out] == ["b"]


class TestLineRoundTrip:
    def test_round_trip_preserves_fields(self):
        s = TimelineSample(T0 * 1000, "tenant-0", passed=5, blocked=2,
                           shed=9, other=1, p99_ms=2.154, max_ms=7.5)
        r = TimelineSample.from_line(s.to_line())
        assert r == s

    def test_none_latency_uses_sentinel(self):
        s = TimelineSample(T0 * 1000, "a", passed=1)
        line = s.to_line()
        # ...|p99|max|waited|completed|exceptions|rtSumMs
        assert line.endswith("|-1|-1|0|0|0|0")
        r = TimelineSample.from_line(line)
        assert r.p99_ms is None and r.max_ms is None

    def test_pre_shaping_8_field_line_parses(self):
        # files written before the waited column existed have 8 fields
        r = TimelineSample.from_line(
            f"{T0 * 1000}|a|5|2|9|1|2.154|7.5")
        assert r.passed == 5 and r.waited == 0
        assert r.p99_ms == 2.154 and r.max_ms == 7.5

    def test_pre_outcome_9_field_line_parses(self):
        # files written before the outcome columns existed have 9 fields
        r = TimelineSample.from_line(
            f"{T0 * 1000}|a|5|2|9|1|2.154|7.5|3")
        assert r.passed == 5 and r.waited == 3
        assert r.completed == 0 and r.exceptions == 0 and r.rt_sum_ms == 0

    def test_namespace_separator_is_escaped(self):
        s = TimelineSample(T0 * 1000, "a|b", passed=1)
        r = TimelineSample.from_line(s.to_line())
        assert r.namespace == "a_b"


class TestFilePersistence:
    def test_writer_searcher_round_trip(self, tmp_path):
        w = TimelineWriter(str(tmp_path))
        w.write([TimelineSample((T0 + d) * 1000, "a", passed=d + 1)
                 for d in range(3)])
        w.close()
        found = TimelineSearcher(str(tmp_path), w.app).find(
            T0 * 1000, (T0 + 2) * 1000)
        assert [s.passed for s in found] == [1, 2, 3]

    def test_time_range_and_namespace_filter(self, tmp_path):
        w = TimelineWriter(str(tmp_path))
        for d in range(4):
            w.write([
                TimelineSample((T0 + d) * 1000, "a", passed=1),
                TimelineSample((T0 + d) * 1000, "b", blocked=1),
            ])
        w.close()
        sr = TimelineSearcher(str(tmp_path), w.app)
        mid = sr.find((T0 + 1) * 1000, (T0 + 2) * 1000)
        assert len(mid) == 4  # 2 seconds x 2 namespaces
        only_b = sr.find(0, (T0 + 9) * 1000, namespace="b")
        assert len(only_b) == 4 and all(s.namespace == "b" for s in only_b)

    def test_rotation_shifts_and_prunes(self, tmp_path):
        w = TimelineWriter(str(tmp_path), single_file_size=200,
                           total_file_count=3)
        for d in range(40):
            w.write([TimelineSample((T0 + d) * 1000, "a", passed=d)])
        w.close()
        files = sorted(p.name for p in tmp_path.iterdir()
                       if not p.name.endswith(".idx"))
        assert files == [f"{w.app}-timeline.log.{n}" for n in range(3)]
        # every data file keeps its second->offset index through renames
        for f in files:
            assert (tmp_path / (f + ".idx")).exists()
        # oldest seconds were rotated off the end; the newest survive
        found = TimelineSearcher(str(tmp_path), w.app).find(
            0, (T0 + 60) * 1000)
        secs = [s.timestamp_ms // 1000 for s in found]
        assert secs == sorted(secs)
        assert T0 + 39 in secs and T0 not in secs

    def test_idx_seek_matches_full_scan(self, tmp_path):
        w = TimelineWriter(str(tmp_path))
        for d in range(50):
            w.write([TimelineSample((T0 + d) * 1000, "a", passed=d)])
        w.close()
        sr = TimelineSearcher(str(tmp_path), w.app)
        late = sr.find((T0 + 45) * 1000, (T0 + 49) * 1000)
        assert [s.passed for s in late] == [45, 46, 47, 48, 49]


class TestMergedFind:
    def test_memory_wins_on_overlap_and_files_extend(self, tmp_path):
        tl = MetricTimeline(window_s=8, writer=TimelineWriter(str(tmp_path)))
        # old seconds: flushed to file, then aged out of the 8s memory ring
        # (T0+24 and T0+25 land on the same ring slots as T0 and T0+1)
        tl.record("a", n_pass=1, now_s=T0)
        tl.record("a", n_pass=2, now_s=T0 + 1)
        tl.flush(upto_s=T0 + 1)
        tl.record("a", n_pass=3, now_s=T0 + 24)  # evicts T0's slot
        tl.record("a", n_pass=4, now_s=T0 + 25)  # evicts T0+1's slot
        assert tl.query(T0 * 1000, (T0 + 1) * 1000) == []  # memory forgot
        tl.flush(upto_s=T0 + 25)
        # the flushed copy of T0+25 is now stale relative to memory
        tl.record("a", n_pass=40, now_s=T0 + 25)
        out = tl.find(T0 * 1000, (T0 + 25) * 1000)
        assert [(s.timestamp_ms // 1000, s.passed) for s in out] == [
            (T0, 1), (T0 + 1, 2), (T0 + 24, 3), (T0 + 25, 44)]

    def test_flush_is_incremental(self, tmp_path):
        tl = MetricTimeline(window_s=60, writer=TimelineWriter(str(tmp_path)))
        tl.record("a", n_pass=1, now_s=T0)
        assert tl.flush(upto_s=T0) == 1
        assert tl.flush(upto_s=T0) == 0  # already on disk

    def test_status_shape(self, tmp_path):
        tl = MetricTimeline(window_s=60, writer=TimelineWriter(str(tmp_path)))
        tl.record("a", n_pass=1, now_s=T0)
        st = tl.status()
        assert st["windowSeconds"] == 60
        assert st["namespaces"] == ["a"]
        assert st["lastSecondMs"] == T0 * 1000
        assert st["fileDir"] == str(tmp_path)


class TestSingletonAndFeed:
    def test_configure_replaces_singleton(self, tmp_path):
        tl = configure_timeline(base_dir=str(tmp_path), window_s=30)
        assert timeline() is tl
        reset_timeline_for_tests()
        assert timeline() is not tl

    def test_verdict_batch_feeds_timeline(self):
        # the single feed point: ServerMetrics.record_verdict_batch ->
        # served rows; SloPlane.record_shed -> shed rows
        m = server_metrics()
        status = np.array([0, 0, 0, 1, 8, 8], np.int8)
        ns_idx = np.array([0, 0, 1, 1, 0, 1], np.int32)
        m.record_verdict_batch(status, ns_idx, ("a", "b"), latency_ms=1.0)
        sums = {s.namespace: s for s in timeline().query()}
        # a: 2 pass, 1 shed(overload); b: 1 pass, 1 block, 1 shed
        assert (sums["a"].passed, sums["a"].blocked, sums["a"].shed) == (
            2, 0, 1)
        assert (sums["b"].passed, sums["b"].blocked, sums["b"].shed) == (
            1, 1, 1)

    def test_timeline_reconciles_with_verdict_counters(self):
        # the scenario harness's reconciliation gate, in miniature: for
        # any sequence of verdict batches, per-namespace timeline
        # pass/block sums equal the sentinel_server_verdicts_total deltas
        m = server_metrics()
        rng = np.random.default_rng(7)
        for _ in range(20):
            n = int(rng.integers(1, 64))
            status = rng.choice(
                np.array([0, 0, 0, 1, 4, 8], np.int8), size=n)
            ns_idx = rng.integers(0, 3, size=n).astype(np.int32)
            m.record_verdict_batch(status, ns_idx, ("a", "b", "c"),
                                   latency_ms=0.5)
        tl_sums = {}
        for s in timeline().query():
            t = tl_sums.setdefault(s.namespace, [0, 0])
            t[0] += s.passed
            t[1] += s.blocked
        counters = m.verdict_totals()
        for ns in ("a", "b", "c"):
            assert tl_sums[ns][0] == counters.get(("pass", ns), 0)
            assert tl_sums[ns][1] == counters.get(("block", ns), 0)

    def test_shed_sums_reconcile_with_slo_plane(self):
        from sentinel_tpu.trace.slo import slo_plane

        plane = slo_plane()
        plane.record_shed("a", "brownout", 5)
        plane.record_shed("a", "queue_full", 2)
        (s,) = timeline().query(namespace="a")
        shed = plane.snapshot()["tenants"]["a"]["shed"]
        assert s.shed == sum(shed.values()) == 7


class TestMetricCommand:
    def test_command_queries_by_range_and_namespace(self):
        import sentinel_tpu.transport.handlers as handlers

        tl = timeline()
        tl.record("a", n_pass=3, now_s=T0)
        tl.record("b", n_block=2, now_s=T0 + 1)
        out = handlers.cmd_cluster_server_metric(
            {"startTime": str(T0 * 1000),
             "endTime": str((T0 + 1) * 1000)}, "")
        assert [(s["namespace"], s["pass"], s["block"]) for s in out] == [
            ("a", 3, 0), ("b", 0, 2)]
        only_b = handlers.cmd_cluster_server_metric(
            {"startTime": "0", "endTime": str((T0 + 9) * 1000),
             "namespace": "b"}, "")
        assert len(only_b) == 1 and only_b[0]["namespace"] == "b"

    def test_command_default_range_and_max_lines(self):
        import sentinel_tpu.transport.handlers as handlers

        tl = timeline()
        for d in range(5):
            tl.record("a", n_pass=1, now_s=T0 + d)
        # endTime defaults to "now": the fixed T0 seconds are in the past
        # relative to the wall clock, so an explicit range is still needed;
        # maxLines caps the result
        out = handlers.cmd_cluster_server_metric(
            {"startTime": str(T0 * 1000), "endTime": str((T0 + 9) * 1000),
             "maxLines": "2"}, "")
        assert len(out) == 2

    def test_stats_command_exposes_timeline_block(self):
        import sentinel_tpu.transport.handlers as handlers

        out = handlers.cmd_cluster_server_stats({}, "")
        assert "timeline" in out
        assert set(out["timeline"]) == {
            "windowSeconds", "namespaces", "lastSecondMs", "fileDir"}
