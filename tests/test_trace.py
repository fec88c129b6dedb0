"""Flight recorder, spans, black-box dumps, and the per-tenant SLO plane."""

import json
import threading

import numpy as np
import pytest

from sentinel_tpu.trace import blackbox
from sentinel_tpu.trace import ring
from sentinel_tpu.trace import spans
from sentinel_tpu.trace.slo import (
    BUDGET_FRACTION,
    SloPlane,
    merge_fleet,
    reset_slo_plane_for_tests,
    slo_plane,
)


@pytest.fixture(autouse=True)
def clean_trace():
    ring.reset_for_tests()
    blackbox.reset_for_tests()
    reset_slo_plane_for_tests()
    yield
    ring.reset_for_tests()
    blackbox.reset_for_tests()
    reset_slo_plane_for_tests()


class TestRing:
    def test_disarmed_is_default(self):
        assert ring.ARMED is False

    def test_record_and_read(self):
        ring.arm(sample=1.0)
        ring.record(ring.CLIENT_IN, xid=42, shard=1, aux=8)
        ring.record(ring.REPLY_OUT, xid=42, shard=1)
        evs = ring.events(xid=42)
        assert [e["stage"] for e in evs] == ["client_in", "reply_out"]
        assert evs[0]["shard"] == 1 and evs[0]["aux"] == 8
        # time-sorted across the (single) ring
        assert evs[0]["t_ns"] <= evs[1]["t_ns"]

    def test_control_events_ignore_sampling(self):
        ring.arm(sample=0.0)  # sample nothing from the data plane
        ring.record(ring.CLIENT_IN, xid=7)
        ring.record(ring.BROWNOUT, aux=1)  # xid=0: control plane
        assert ring.events(xid=7) == []
        assert [e["stage"] for e in ring.events()] == ["brownout"]

    def test_record_many_honors_sample(self):
        ring.arm(sample=0.0)
        ring.record_many(ring.DISPATCH, [1, 2, 3])
        assert ring.events() == []
        ring.arm(sample=1.0)
        ring.record_many(ring.DISPATCH, np.array([1, 2, 3]), aux=3)
        assert sorted(e["xid"] for e in ring.events()) == [1, 2, 3]

    def test_wrap_evicts_oldest(self):
        ring.arm(sample=1.0)
        cap = ring.DEFAULT_RING_EVENTS
        for i in range(cap + 10):
            ring.record(ring.ENQUEUE, xid=i + 1)
        st = ring.status()
        assert st["threads"][0]["events"] == cap
        assert st["threads"][0]["dropped"] == 10
        evs = ring.events()
        assert len(evs) == cap
        # the 10 oldest xids were overwritten, the newest survive
        xids = {e["xid"] for e in evs}
        assert 1 not in xids and 10 not in xids
        assert cap + 10 in xids
        # rows() preserved oldest→newest order through the wrap
        assert evs[0]["xid"] == 11 and evs[-1]["xid"] == cap + 10

    def test_torn_tail_rows_dropped(self):
        # a thread that died mid-record leaves a zeroed/torn row; readers
        # must treat the ring as advisory and drop t_ns==0 rows
        ring.arm(sample=1.0)
        ring.record(ring.CLIENT_IN, xid=5)
        ring.record(ring.REPLY_OUT, xid=5)
        r = ring._TLS.ring
        r.buf[1]["t_ns"] = 0  # tear the second row
        evs = ring.events()
        assert [e["stage"] for e in evs] == ["client_in"]

    def test_dead_thread_ring_still_readable(self):
        ring.arm(sample=1.0)

        def worker():
            ring.record(ring.DISPATCH, xid=99)

        t = threading.Thread(target=worker, name="dead-lane")
        t.start()
        t.join()
        evs = ring.events(xid=99)
        assert len(evs) == 1 and evs[0]["thread"] == "dead-lane"

    def test_sampled_xids_newest_first(self):
        ring.arm(sample=1.0)
        for x in (10, 20, 30):
            ring.record(ring.CLIENT_IN, xid=x)
        ring.record(ring.CLIENT_IN, xid=20)  # re-seen: now the newest
        assert ring.sampled_xids() == [20, 30, 10]
        assert ring.sampled_xids(limit=1) == [20]

    def test_status_shape(self):
        ring.arm(sample=0.25)
        ring.record(ring.HIER)
        st = ring.status()
        assert st["armed"] is True
        assert st["sample"] == 0.25
        assert st["totalEvents"] == 1
        ring.disarm()
        assert ring.status()["armed"] is False


class TestSpans:
    def _request(self, xid):
        ring.record(ring.CLIENT_IN, xid=xid)
        ring.record(ring.ENQUEUE, xid=xid)
        ring.record(ring.DISPATCH, xid=xid)
        ring.record(ring.REPLY_OUT, xid=xid)

    def test_complete_span(self):
        ring.arm(sample=1.0)
        self._request(101)
        sp = spans.assemble(101)
        assert sp["complete"] is True
        assert sp["stages"] == ["client_in", "enqueue", "dispatch",
                                "reply_out"]
        assert sp["durationUs"] >= 0

    def test_shed_is_a_complete_exit(self):
        ring.arm(sample=1.0)
        ring.record(ring.CLIENT_IN, xid=102)
        ring.record(ring.SHED, xid=102)
        assert spans.assemble(102)["complete"] is True

    def test_incomplete_span(self):
        ring.arm(sample=1.0)
        ring.record(ring.CLIENT_IN, xid=103)
        ring.record(ring.DISPATCH, xid=103)  # reply never recorded
        sp = spans.assemble(103)
        assert sp["complete"] is False

    def test_unsampled_xid_returns_none(self):
        ring.arm(sample=1.0)
        self._request(104)
        assert spans.assemble(9999) is None

    def test_wrapped_ring_yields_incomplete_not_crash(self):
        # the entry hop was evicted by ring wrap → the span is honest
        # about the missing stage instead of raising
        ring.arm(sample=1.0)
        ring.record(ring.CLIENT_IN, xid=105)
        for i in range(ring.DEFAULT_RING_EVENTS):
            ring.record(ring.ENQUEUE, xid=1_000_000 + i)
        ring.record(ring.REPLY_OUT, xid=105)
        sp = spans.assemble(105)
        assert sp is not None and sp["complete"] is False
        assert "client_in" not in sp["stages"]

    def test_assemble_recent_and_completeness(self):
        ring.arm(sample=1.0)
        self._request(201)
        self._request(202)
        ring.record(ring.CLIENT_IN, xid=203)  # torn: no exit
        assembled = spans.assemble_recent()
        assert len(assembled) == 3
        comp = spans.completeness(assembled)
        assert comp == {"spans": 3, "complete": 2, "fraction": 2 / 3}
        assert spans.completeness([])["fraction"] is None

    def test_write_artifact(self, tmp_path):
        ring.arm(sample=1.0)
        self._request(301)
        path = spans.write_artifact(str(tmp_path / "spans.json"))
        with open(path) as f:
            doc = json.load(f)
        assert doc["schema"] == "sentinel-trace-spans/1"
        assert doc["completeness"]["complete"] == 1
        assert doc["build"]["version"]
        assert doc["spans"][0]["xid"] == 301


class TestBlackbox:
    def test_dump_parses_with_full_payload(self, tmp_path):
        ring.arm(sample=1.0)
        ring.record(ring.CLIENT_IN, xid=11)
        slo_plane().record("ns-a", 1.0, n=4)
        path = blackbox.dump("unit_test", directory=str(tmp_path))
        with open(path) as f:
            doc = json.load(f)
        assert doc["schema"] == "sentinel-blackbox/1"
        assert doc["reason"] == "unit_test"
        assert len(doc["configFingerprint"]) == 16
        assert doc["trace"]["armed"] is True
        assert any(e["xid"] == 11 for e in doc["events"])
        assert "ns-a" in doc["slo"]["tenants"]
        assert "verdicts" in doc["metrics"]
        assert doc["build"]["wire_rev"]

    def test_dump_requires_a_directory(self):
        with pytest.raises(ValueError):
            blackbox.dump("no_dir")

    def test_maybe_dump_noop_unconfigured(self):
        assert blackbox.maybe_dump("brownout:shed_low") is None
        assert blackbox.dumps_written == 0

    def test_maybe_dump_rate_limited(self, tmp_path):
        blackbox.configure(str(tmp_path), min_interval_s=3600.0)
        first = blackbox.maybe_dump("brownout:shed_low")
        assert first is not None
        assert blackbox.maybe_dump("brownout:degrade") is None
        assert blackbox.dumps_written == 1
        assert blackbox.last_path == first

    def test_first_dump_on_a_machine_just_booted(self, tmp_path, monkeypatch):
        # time.monotonic() counts from boot on Linux: under min_interval_s
        # of uptime a last-dump stamp of 0.0 refused the first dump
        import time as _time

        monkeypatch.setattr(_time, "monotonic", lambda: 12.5)
        blackbox.configure(str(tmp_path), min_interval_s=3600.0)
        assert blackbox.maybe_dump("brownout:shed_low") is not None
        assert blackbox.maybe_dump("brownout:degrade") is None
        assert blackbox.dumps_written == 1

    def test_config_fingerprint_tracks_config(self):
        from sentinel_tpu.core.config import SentinelConfig

        a = blackbox.config_fingerprint()
        SentinelConfig.set("sentinel.tpu.test.fingerprint", "x")
        try:
            assert blackbox.config_fingerprint() != a
        finally:
            with SentinelConfig._lock:
                SentinelConfig._props.pop("sentinel.tpu.test.fingerprint",
                                          None)
        assert blackbox.config_fingerprint() == a


class TestSloPlane:
    def test_record_and_snapshot(self):
        p = SloPlane(objective_ms=2.0)
        p.record("ns-a", 1.0, n=90)
        p.record("ns-a", 5.0, n=10)
        snap = p.snapshot()
        t = snap["tenants"]["ns-a"]
        assert snap["objectiveMs"] == 2.0
        assert t["count"] == 100
        assert t["windows"]["1m"] == {"total": 100, "over": 10}
        # 10% over a 1% budget → burn 10 on both windows
        assert t["burnRate"]["1m"] == pytest.approx(10.0)
        assert t["burnRate"]["1h"] == pytest.approx(10.0)
        assert t["p99Ms"] >= 1.0

    def test_burn_window_expiry(self):
        p = SloPlane(objective_ms=2.0)
        p.record("ns-a", 5.0, n=10, now_s=1000)
        total, over = p._tenants["ns-a"].windows["1m"].totals(now_s=1030)
        assert (total, over) == (10, 10)
        total, over = p._tenants["ns-a"].windows["1m"].totals(now_s=1061)
        assert (total, over) == (0, 0)  # aged out of the 1m window
        total, over = p._tenants["ns-a"].windows["1h"].totals(now_s=1061)
        assert (total, over) == (10, 10)  # still inside the 1h window

    def test_shed_burns_whole_budget(self):
        p = SloPlane(objective_ms=2.0)
        p.record_shed("ns-b", "overload", n=5)
        snap = p.snapshot()["tenants"]["ns-b"]
        assert snap["shed"] == {"overload": 5}
        assert snap["windows"]["1m"] == {"total": 5, "over": 5}
        assert p.burn_rates("ns-b")["1m"] == pytest.approx(1 / BUDGET_FRACTION)
        assert p.burn_rates("missing")["1m"] is None

    def test_record_shed_indexed(self):
        p = SloPlane(objective_ms=2.0)
        ns_idx = np.array([0, 0, 1, -1], dtype=np.int32)
        p.record_shed_indexed(ns_idx, ("flood", "steady"), "queue_full")
        snap = p.snapshot()["tenants"]
        assert snap["flood"]["shed"] == {"queue_full": 2}
        assert snap["steady"]["shed"] == {"queue_full": 1}
        assert snap["(no-rule)"]["shed"] == {"queue_full": 1}

    def test_render_series(self):
        p = SloPlane(objective_ms=2.0)
        p.record("ns-a", 5.0, n=10)
        p.record_shed("ns-a", "brownout", n=3)
        text = p.render()
        assert "sentinel_slo_objective_ms 2" in text
        assert 'sentinel_slo_latency_ms_bucket{namespace="ns-a"' in text
        assert 'sentinel_slo_burn_rate{namespace="ns-a",window="1m"}' in text
        assert 'sentinel_slo_shed_total{namespace="ns-a",reason="brownout"} 3' \
            in text

    def test_singleton_reads_configured_objective(self):
        from sentinel_tpu.core.config import SentinelConfig
        from sentinel_tpu.trace.slo import KEY_OBJECTIVE_MS

        SentinelConfig.set(KEY_OBJECTIVE_MS, "50")
        try:
            reset_slo_plane_for_tests()
            assert slo_plane().objective_ms == 50.0
        finally:
            with SentinelConfig._lock:
                SentinelConfig._props.pop(KEY_OBJECTIVE_MS, None)


class TestMergeFleet:
    def _pod(self, total, over, count=None, p99=1.0, shed=None):
        return {"objectiveMs": 2.0, "tenants": {"ns-a": {
            "count": count if count is not None else total,
            "p99Ms": p99,
            "windows": {"1m": {"total": total, "over": over},
                        "1h": {"total": total, "over": over}},
            "shed": shed or {},
        }}}

    def test_sums_windows_and_recomputes_burn(self):
        # pod A: 100 rows none over; pod B: 100 rows all over.
        # a mean of per-pod burns would say 50× regardless of load split;
        # the merged burn must come from the SUMMED windows
        merged = merge_fleet([self._pod(100, 0), self._pod(100, 100)])
        t = merged["tenants"]["ns-a"]
        assert t["windows"]["1m"] == {"total": 200, "over": 100}
        assert t["burnRate"]["1m"] == pytest.approx(50.0)
        assert t["count"] == 200

    def test_keeps_worst_p99_and_sums_shed(self):
        merged = merge_fleet([
            self._pod(10, 0, p99=1.5, shed={"overload": 3}),
            self._pod(10, 0, p99=8.0, shed={"overload": 4, "brownout": 1}),
        ])
        t = merged["tenants"]["ns-a"]
        assert t["p99Ms"] == 8.0
        assert t["shed"] == {"overload": 7, "brownout": 1}

    def test_malformed_pod_contributes_nothing(self):
        merged = merge_fleet([
            self._pod(10, 5), "not-a-snapshot", {"tenants": None}, None,
        ])
        t = merged["tenants"]["ns-a"]
        assert t["windows"]["1m"] == {"total": 10, "over": 5}

    def test_live_snapshot_roundtrip(self):
        a = SloPlane(objective_ms=2.0)
        b = SloPlane(objective_ms=2.0)
        a.record("ns-x", 1.0, n=50)
        b.record("ns-x", 9.0, n=50)
        merged = merge_fleet([a.snapshot(), b.snapshot()])
        t = merged["tenants"]["ns-x"]
        assert t["count"] == 100
        assert t["burnRate"]["1m"] == pytest.approx(50.0)


class TestTransportCommands:
    def _route(self, path, params, body=""):
        import sentinel_tpu.transport.handlers  # noqa: F401
        from sentinel_tpu.transport.command import _route

        code, payload, ctype = _route("GET", path, params, body)
        assert code == 200
        return json.loads(payload)

    def test_trace_arm_status_disarm(self):
        out = self._route("cluster/server/trace",
                          {"action": "arm", "sample": "0.5"})
        assert out["armed"] is True and out["sample"] == 0.5
        assert ring.ARMED is True
        out = self._route("cluster/server/trace", {"action": "disarm"})
        assert out["armed"] is False
        assert ring.ARMED is False

    def test_trace_spans_and_blackbox(self, tmp_path):
        ring.arm(sample=1.0)
        ring.record(ring.CLIENT_IN, xid=77)
        ring.record(ring.REPLY_OUT, xid=77)
        out = self._route("cluster/server/trace",
                          {"action": "spans", "xid": "77"})
        assert out["complete"] is True
        out = self._route("cluster/server/trace",
                          {"action": "spans", "xid": "0x4D"})  # hex = 77
        assert out["xid"] == 77
        out = self._route("cluster/server/trace", {"action": "spans"})
        assert out["completeness"]["spans"] == 1
        out = self._route("cluster/server/trace",
                          {"action": "spans", "dir": str(tmp_path)})
        assert json.load(open(out["path"]))["schema"] == \
            "sentinel-trace-spans/1"
        out = self._route("cluster/server/trace",
                          {"action": "blackbox", "dir": str(tmp_path)})
        assert json.load(open(out["path"]))["schema"] == "sentinel-blackbox/1"
        # no dir configured and none passed → clean error, not a 500
        blackbox.reset_for_tests()
        out = self._route("cluster/server/trace", {"action": "blackbox"})
        assert "error" in out

    def test_slo_local_and_fleet(self):
        slo_plane().record("ns-a", 5.0, n=10)
        out = self._route("cluster/server/slo", {"action": "local"})
        assert "ns-a" in out["tenants"]
        pods = json.dumps([out, {"slo": out}, "garbage"])
        merged = self._route("cluster/server/slo", {"action": "fleet"},
                             body=pods)
        assert merged["pods"] == 3
        assert merged["tenants"]["ns-a"]["count"] == 20

    def test_cluster_server_stats_carries_trace_slo_build(self):
        out = self._route("clusterServerStats", {})
        assert "armed" in out["trace"]
        assert "tenants" in out["slo"]
        assert out["buildInfo"]["version"]
