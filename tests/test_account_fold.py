"""The account half by count matrix (PR 46): a dispatch deposits one bincount
into ``ServerMetrics``'s pending record, and the per-namespace fan-out to the
verdict dict, the SLO plane and the timeline is folded once a wall second and
before every read.

The reference below is the code this replaced, ``record_verdict_batch`` +
``_feed_slo`` as they stood at PR 45, kept HERE and not in the package: the
fan-out done per dispatch into sinks of its own. Deposit + fold are held to
its answers.
"""

import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from sentinel_tpu.cluster import token_service as ts
from sentinel_tpu.engine.decide import TokenStatus
from sentinel_tpu.metrics import exporter
from sentinel_tpu.metrics.histogram import LatencyHistogram
from sentinel_tpu.metrics.server import (
    NO_RULE_NAMESPACE,
    VERDICT_NAMES,
    reset_server_metrics_for_tests,
    server_metrics,
)
from sentinel_tpu.metrics.timeline import MetricTimeline, timeline
from sentinel_tpu.trace import blackbox
from sentinel_tpu.trace.slo import SloPlane, slo_plane
from sentinel_tpu.transport import handlers

S = TokenStatus
OBJECTIVE_MS = 2.0  # sentinel.tpu.slo.p99.ms's default


@pytest.fixture(autouse=True)
def fresh():
    reset_server_metrics_for_tests()
    yield
    reset_server_metrics_for_tests()


# -- the reference: PR 45's per-dispatch fan-out ------------------------------
_SLO_SHED_REASONS = {"overload": "overload", "too_many_request":
                     "namespace_guard", "moved": "moved",
                     "degraded": "degraded"}


class Reference:
    """PR 45's ``record_verdict_batch`` and ``_feed_slo``, body for body,
    over sinks of its own. The one change: where the sinks read the wall
    clock themselves, ``now_s`` goes in (``SloPlane.record_shed`` had no
    such argument, so its three lines stand here)."""

    def __init__(self):
        self.verdicts = {}
        self.wait_assigned = 0
        self.wait_assigned_ms = LatencyHistogram(lo=1.0, hi=60_000.0)
        self.plane = SloPlane()
        self.timeline = MetricTimeline()
        self.stat_log = {}

    def record_verdict_batch(self, status, ns_idx, ns_names, latency_ms=None,
                             wait_ms=None, now_s=None):
        status = np.asarray(status)
        n = int(status.shape[0])
        if n == 0:
            return
        if wait_ms is not None:
            w = np.asarray(wait_ms)
            wmask = w > 0
            n_wait = int(wmask.sum())
            if n_wait:
                self.wait_assigned += n_wait
                for v, c in zip(*np.unique(w[wmask], return_counts=True)):
                    self.wait_assigned_ms.record(float(v), int(c))
        updates = {}
        for code, vname in VERDICT_NAMES.items():
            mask = status == code
            hits = int(mask.sum())
            if not hits:
                continue
            if ns_idx is None or not len(ns_names):
                updates[(vname, NO_RULE_NAMESPACE)] = hits
                continue
            counts = np.bincount(
                ns_idx[mask] + 1, minlength=len(ns_names) + 1
            )
            if counts[0]:
                updates[(vname, NO_RULE_NAMESPACE)] = int(counts[0])
            for j in np.nonzero(counts[1:])[0]:
                updates[(vname, ns_names[int(j)])] = int(counts[1 + j])
        for key, v in updates.items():
            self.verdicts[key] = self.verdicts.get(key, 0) + v
        self._feed_slo(updates, latency_ms, now_s)
        # token_service._account's five scans of status
        for event, code in (
            ("pass", int(S.OK)), ("block", int(S.BLOCKED)),
            ("occupied", int(S.SHOULD_WAIT)),
            ("tooManyRequest", int(S.TOO_MANY_REQUEST)),
            ("degraded", int(S.DEGRADED)),
        ):
            hits = int((status == code).sum())
            if hits:
                self.stat_log[event] = self.stat_log.get(event, 0) + hits

    def _record_shed(self, ns, reason, n, now_s):
        t = self.plane._tenant(ns)
        t.shed[reason] = t.shed.get(reason, 0) + n
        for w in t.windows.values():
            w.record(n, n, now_s)
        self.timeline.record(ns, n_shed=n, now_s=now_s)

    def _feed_slo(self, updates, latency_ms, now_s):
        plane, tl = self.plane, self.timeline
        served = {}
        cols = {}
        for (vname, ns), v in updates.items():
            reason = _SLO_SHED_REASONS.get(vname)
            if reason is not None:
                self._record_shed(ns, reason, v, now_s)
                continue
            served[ns] = served.get(ns, 0) + v
            c = cols.setdefault(ns, [0, 0, 0, 0])
            if vname == "pass":
                c[0] += v
            elif vname == "block":
                c[1] += v
            elif vname == "should_wait":
                c[3] += v
                plane.record_waited(ns, v)
            else:
                c[2] += v
        for ns, c in cols.items():
            tl.record(ns, n_pass=c[0], n_block=c[1], n_other=c[2],
                      latency_ms=latency_ms, n_waited=c[3], now_s=now_s)
        if latency_ms is not None:
            for ns, v in served.items():
                plane.record(ns, latency_ms, v, now_s=now_s)


# -- traffic ------------------------------------------------------------------
# OK, BLOCKED, SHOULD_WAIT, NO_RULE_EXISTS, TOO_MANY_REQUEST, FAIL, OVERLOAD,
# MOVED, DEGRADED, and 11, which names no verdict and is counted nowhere
MIX = np.array([0, 0, 0, 0, 1, 1, 2, 2, 3, 4, 5, 8, 10, 12, 11], np.int8)


def _dispatch(rng, rows, n_ns):
    """One dispatch's ``(status, ns_idx, wait)``: every status of ``MIX``,
    SHOULD_WAIT with positive and with zero waits, slots of -1."""
    status = rng.choice(MIX, size=rows)
    wait = np.where(status == int(S.SHOULD_WAIT),
                    rng.choice(np.array([0, 1, 3, 3, 250, 70_000]), rows),
                    0).astype(np.int32)
    ns_idx = (rng.integers(-1, n_ns, rows).astype(np.int32) if n_ns
              else None)
    return status, ns_idx, wait


def _names(n_ns, tag):
    return tuple(f"{tag}{i}" for i in range(n_ns))


LATENCIES = {
    "under": (0.35, 0.9, 1.7),
    "at": (OBJECTIVE_MS,),
    "over": (2.4, 31.0, 12_000.0),  # the last past both histograms' bounds
    "none": (None,),
    "mixed": (0.35, OBJECTIVE_MS, None, 31.0),
}


def _schedule(sec, n_ns):
    """(second, names) per dispatch: several inside one second, across a
    second's edge, across an ``ns_names`` swap inside a second, and back."""
    a, b = _names(n_ns, "a"), _names(n_ns, "b")
    return [(sec, a), (sec, a), (sec, a), (sec + 1, a), (sec + 1, a),
            (sec + 1, b), (sec + 1, b), (sec + 2, a)]


def _windows(plane):
    return {ns: {name: (w._stamp, w._total, w._over)
                 for name, w in t.windows.items()}
            for ns, t in plane._tenants.items()}


@pytest.mark.parametrize("latency", sorted(LATENCIES))
@pytest.mark.parametrize("n_ns", [0, 8, 62])
@pytest.mark.parametrize("rows", [1, 212, 1024, 4096])
def test_deposit_and_fold_give_what_the_fan_out_per_dispatch_gave(
        rows, n_ns, latency):
    rng = np.random.default_rng(rows * 1000 + n_ns)
    sm, ref = server_metrics(), Reference()
    by_code_total = np.zeros(max(VERDICT_NAMES) + 1, np.int64)
    sec = int(time.time()) - 2  # inside the burn windows a snapshot reads
    for i, (now_s, names) in enumerate(_schedule(sec, n_ns)):
        status, ns_idx, wait = _dispatch(rng, rows, n_ns)
        lat = LATENCIES[latency][i % len(LATENCIES[latency])]
        by_code_total += sm.record_verdict_batch(
            status, ns_idx, names, latency_ms=lat, wait_ms=wait, now_s=now_s)
        ref.record_verdict_batch(
            status, ns_idx, names, latency_ms=lat, wait_ms=wait, now_s=now_s)

    got = {(v["verdict"], v["namespace"]): v["count"]
           for v in sm.snapshot()["verdicts"]}
    assert got == ref.verdicts
    assert sm.wait_assigned_total == ref.wait_assigned
    assert sm.wait_assigned_ms.snapshot() == ref.wait_assigned_ms.snapshot()
    assert sm.wait_assigned_ms._frozen() == ref.wait_assigned_ms._frozen()

    snap, want = slo_plane().snapshot(), ref.plane.snapshot()
    assert sorted(snap["tenants"]) == sorted(want["tenants"])
    assert snap == want  # counts, quantiles, max, windows, shed, waited
    assert _windows(slo_plane()) == _windows(ref.plane)  # second by second
    for ns, t in ref.plane._tenants.items():
        counts, total, s, vmax = slo_plane()._tenants[ns].hist._frozen()
        r_counts, r_total, r_s, r_max = t.hist._frozen()
        assert (counts, total, vmax) == (r_counts, r_total, r_max)
        assert s == pytest.approx(r_s, rel=1e-9)

    assert timeline().query() == ref.timeline.query()
    assert timeline().namespaces() == ref.timeline.namespaces()

    events = {"pass": S.OK, "block": S.BLOCKED, "occupied": S.SHOULD_WAIT,
              "tooManyRequest": S.TOO_MANY_REQUEST, "degraded": S.DEGRADED}
    assert {e: int(by_code_total[c]) for e, c in events.items()
            if by_code_total[c]} == ref.stat_log


# -- the account half itself: attribution, stat log, breaker scan -------------
def _fake_service(names, slot_ns, scans):
    return SimpleNamespace(_ns_snapshot=(names, slot_ns), _trace_sid=0,
                           _breaker_scan=lambda: scans.append(1))


@pytest.mark.parametrize("degraded", [False, True])
def test_the_account_half_counts_the_stat_log_from_the_matrix(
        monkeypatch, degraded):
    logged, scans = {}, []
    monkeypatch.setattr(
        ts, "log_cluster",
        lambda event, flow_id=-1, count=1: logged.__setitem__(
            event, logged.get(event, 0) + count))
    names = ("a", "b")
    slot_ns = np.array([0, 1, 1, -1, -1], np.int32)  # 4 slots + slot -1's
    status = np.array([0, 0, 1, 2, 4, 12 if degraded else 0, 3], np.int8)
    slots = np.array([0, 1, 2, 2, 0, 1, -1], np.int32)
    wait = np.array([0, 0, 0, 7, 0, 0, 0], np.int32)
    ts.DefaultTokenService._account(
        _fake_service(names, slot_ns, scans), status, wait, slots, 1,
        status.size, 0, 10, 20, 1_500_000)
    want = {"pass": 2 if degraded else 3, "block": 1, "occupied": 1,
            "tooManyRequest": 1}
    if degraded:
        want["degraded"] = 1
    assert logged == want
    assert len(scans) == int(degraded)
    totals = server_metrics().verdict_totals()
    assert totals[("pass", "a")] == 1
    assert totals[("should_wait", "b")] == 1
    assert totals[("no_rule", NO_RULE_NAMESPACE)] == 1  # slot -1
    assert totals[("too_many_request", "a")] == 1
    assert server_metrics().wait_assigned_total == 1
    tenant = slo_plane().snapshot()["tenants"]["b"]
    assert tenant["count"] == 3 + (not degraded)  # served rows at 1.5 ms
    assert tenant["maxMs"] == pytest.approx(1.5)


def test_a_fused_span_and_a_param_dispatch_are_accounted_alike():
    scans = []
    fake = _fake_service(("a",), np.array([0, 0, -1], np.int32), scans)
    status = np.zeros(6, np.int8)
    frames = [np.array([0, 1, -1], np.int32), np.array([1, 1, 0], np.int32)]
    ts.DefaultTokenService._account(
        fake, status, np.zeros(6, np.int32), frames, 1, 6, 0, 1, 2, 3)
    ts.DefaultTokenService._account(
        fake, status[:2], None, None, 2, 2, 0, 1, 2, 3)  # no slots: param
    assert server_metrics().verdict_totals() == {
        ("pass", "a"): 5, ("pass", NO_RULE_NAMESPACE): 3}


# -- every reader folds first -------------------------------------------------
def _deposit(ns="tenant-a", rows=5, latency_ms=1.0):
    server_metrics().record_verdict_batch(
        np.array([0] * (rows - 2) + [2, 8], np.int8),
        np.zeros(rows, np.int32), (ns,), latency_ms=latency_ms,
        wait_ms=np.array([0] * (rows - 2) + [9, 0], np.int32))


def _stats_command():
    return handlers.cmd_cluster_server_stats({}, "")


READERS = {
    "snapshot": lambda: {
        (v["verdict"], v["namespace"]): v["count"]
        for v in server_metrics().snapshot()["verdicts"]
    }[("pass", "tenant-a")] == 3,
    "snapshot_wait_total": lambda: (
        server_metrics().snapshot()["waitAssignedTotal"] == 1),
    "stage_snapshot": lambda: (
        server_metrics().stage_snapshot()["wait_assigned_ms"]["count"] == 1),
    "render": lambda: (
        'sentinel_server_verdicts_total{verdict="pass",'
        'namespace="tenant-a"} 3' in server_metrics().render()),
    "exporter": lambda: (
        'sentinel_slo_shed_total{namespace="tenant-a",reason="overload"} 1'
        in exporter.render()
        and "sentinel_server_wait_assigned_total 1" in exporter.render()),
    "verdict_totals": lambda: (
        server_metrics().verdict_totals()[("overload", "tenant-a")] == 1),
    "verdict_totals_by_namespace": lambda: (
        server_metrics().verdict_totals_by_namespace() == {"tenant-a": 5}),
    "wait_assigned_total": lambda: (
        server_metrics().wait_assigned_total == 1),
    "wait_assigned_ms": lambda: (
        server_metrics().wait_assigned_ms.snapshot()["max"] == 9.0),
    "slo_snapshot": lambda: (
        slo_plane().snapshot()["tenants"]["tenant-a"]["count"] == 4),
    "slo_render": lambda: (
        'sentinel_slo_waited_total{namespace="tenant-a"} 1'
        in slo_plane().render()),
    "slo_burn_rates": lambda: (
        slo_plane().burn_rates("tenant-a")["1m"] == pytest.approx(20.0)),
    "timeline_status": lambda: (
        timeline().status()["namespaces"] == ["tenant-a"]),
    "timeline_query": lambda: [
        (s.passed, s.waited, s.shed) for s in timeline().query()
    ] == [(3, 1, 1)],
    "timeline_find": lambda: [
        (s.passed, s.waited, s.shed) for s in timeline().find()
    ] == [(3, 1, 1)],
    "timeline_namespaces": lambda: timeline().namespaces() == ["tenant-a"],
    "blackbox": lambda: (
        blackbox._document("test", None)["slo"]["tenants"]["tenant-a"]
        ["shed"] == {"overload": 1}),
    "stats_command": lambda: (
        _stats_command()["slo"]["tenants"]["tenant-a"]["waited"] == 1
        and _stats_command()["timeline"]["namespaces"] == ["tenant-a"]),
    "metric_command": lambda: [
        s["pass"] for s in handlers.cmd_cluster_server_metric({}, "")
    ] == [3],
    "slo_command": lambda: (
        handlers.cmd_cluster_server_slo({}, "")["tenants"]["tenant-a"]
        ["windows"]["1m"] == {"total": 5, "over": 1}),
}


@pytest.mark.parametrize("reader", sorted(READERS))
def test_a_deposit_made_the_line_before_is_in_what_the_reader_returns(reader):
    _deposit()
    assert READERS[reader]()


def test_a_flush_writes_the_deposits_of_the_seconds_it_completes(tmp_path):
    from sentinel_tpu.metrics.timeline import (
        TimelineSearcher,
        configure_timeline,
    )

    tl = configure_timeline(base_dir=str(tmp_path), window_s=30)
    sec = int(time.time()) - 3
    server_metrics().record_verdict_batch(
        np.zeros(4, np.int8), None, (), latency_ms=1.0, now_s=sec)
    assert tl.flush(upto_s=sec) == 1
    (line,) = TimelineSearcher(str(tmp_path), tl.writer.app).find(
        sec * 1000, sec * 1000 + 999)
    assert (line.namespace, line.passed) == (NO_RULE_NAMESPACE, 4)


def test_a_direct_record_of_the_next_second_flushes_the_pending_one(tmp_path):
    # a door-level shed writes straight through; the second it completes
    # must reach the file with the dispatches deposited in it
    from sentinel_tpu.metrics.timeline import (
        TimelineSearcher,
        configure_timeline,
    )

    tl = configure_timeline(base_dir=str(tmp_path), window_s=30)
    sec = int(time.time()) - 3
    server_metrics().record_verdict_batch(
        np.zeros(4, np.int8), None, (), now_s=sec)
    tl.record("door", n_shed=1, now_s=sec + 1)
    (line,) = TimelineSearcher(str(tmp_path), tl.writer.app).find(
        sec * 1000, sec * 1000 + 999)
    assert (line.namespace, line.passed) == (NO_RULE_NAMESPACE, 4)


def test_a_reset_drops_what_nobody_read():
    _deposit()
    server_metrics().reset()
    assert server_metrics().snapshot()["verdicts"] == []
    assert server_metrics().wait_assigned_total == 0
    assert slo_plane().snapshot()["tenants"] == {}
    assert timeline().query() == []


def test_the_sinks_that_go_take_what_was_deposited_for_them():
    from sentinel_tpu.metrics.timeline import reset_timeline_for_tests
    from sentinel_tpu.trace.slo import reset_slo_plane_for_tests

    old_plane, old_tl = slo_plane(), timeline()
    _deposit()
    reset_slo_plane_for_tests()
    reset_timeline_for_tests()
    assert "tenant-a" in old_plane._tenants and "tenant-a" in old_tl._rings
    assert slo_plane().snapshot()["tenants"] == {}
    assert timeline().query() == []


def test_direct_writers_and_the_fold_add_up():
    sm = server_metrics()
    sm.count_verdict("pass", "tenant-a", 10)
    sm.count_rls("d", 2, 1)
    slo_plane().record_shed("tenant-a", "queue_full", 4)
    slo_plane().record_shed_indexed(
        np.array([0, 0, -1], np.int32), ("tenant-a",), "brownout")
    _deposit()
    totals = sm.verdict_totals()
    assert totals[("pass", "tenant-a")] == 13
    assert totals[("pass", "rls:d")] == 2
    tenant = slo_plane().snapshot()["tenants"]["tenant-a"]
    assert tenant["shed"] == {"queue_full": 4, "brownout": 2, "overload": 1}
    assert tenant["windows"]["1m"] == {"total": 11, "over": 7}
    assert sum(s.shed for s in timeline().query(namespace="tenant-a")) == 7


# -- one fold a second, not one a deposit -------------------------------------
def test_the_deposits_of_one_second_are_one_fold():
    sm = server_metrics()
    sec = int(time.time())
    names = ("a", "b")
    for _ in range(50):
        sm.record_verdict_batch(np.zeros(8, np.int8), np.zeros(8, np.int32),
                                names, latency_ms=1.0, now_s=sec)
        # a param dispatch has (no-rule)'s column alone: same record
        sm.record_verdict_batch(np.zeros(3, np.int8), None, (),
                                latency_ms=1.0, now_s=sec)
    assert sm.account_folds_total == 0  # reading it folds nothing
    assert sm.stage_snapshot()["account_folds_total"] == 1
    assert sm.snapshot()["accountFoldsTotal"] == 1  # nothing pending: no fold
    assert "sentinel_server_account_folds_total 1" in sm.render()
    assert sm.verdict_totals() == {
        ("pass", "a"): 400, ("pass", NO_RULE_NAMESPACE): 150}


def test_a_later_second_and_another_snapshot_fold_the_record_before():
    sm = server_metrics()
    sec = int(time.time()) - 1
    args = (np.zeros(8, np.int8), np.zeros(8, np.int32))
    sm.record_verdict_batch(*args, ("a",), now_s=sec)
    sm.record_verdict_batch(*args, ("a",), now_s=sec)
    assert sm.account_folds_total == 0
    sm.record_verdict_batch(*args, ("a",), now_s=sec + 1)
    assert sm.account_folds_total == 1
    sm.record_verdict_batch(*args, ("b",), now_s=sec + 1)  # rules reloaded
    assert sm.account_folds_total == 2
    sm.record_verdict_batch(*args, ("b",), now_s=sec + 1)  # an equal tuple
    assert sm.account_folds_total == 2
    assert sm.verdict_totals() == {("pass", "a"): 24, ("pass", "b"): 16}
    assert [(s.timestamp_ms // 1000 - sec, s.namespace, s.passed)
            for s in timeline().query()] == [
        (0, "a", 16), (1, "a", 8), (1, "b", 16)]


# -- conservation under threads -----------------------------------------------
def test_four_depositors_and_a_reader_lose_and_invent_nothing():
    sm = server_metrics()
    names = _names(8, "t")
    per_thread, rows = 300, 64
    stop = threading.Event()
    seen, errors = [], []

    def deposit(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(per_thread):
                status, ns_idx, wait = _dispatch(rng, rows, len(names))
                status[status == 11] = 0  # every row a verdict
                sm.record_verdict_batch(status, ns_idx, names,
                                        latency_ms=1.0, wait_ms=wait)
        except Exception as e:  # pragma: no cover - the assertion below
            errors.append(e)

    def read():
        while not stop.is_set():
            verdicts = sum(v["count"] for v in sm.snapshot()["verdicts"])
            tenants = slo_plane().snapshot()["tenants"].values()
            seen.append((verdicts, sum(
                t["windows"]["1h"]["total"] for t in tenants)))

    threads = [threading.Thread(target=deposit, args=(s,)) for s in range(4)]
    reader = threading.Thread(target=read)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # hand the GIL over inside every deposit
    try:
        reader.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        stop.set()
        reader.join(timeout=120)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads + [reader])
    deposited = 4 * per_thread * rows
    assert not errors
    assert seen and all(v <= deposited and w <= deposited for v, w in seen)
    assert [v for v, _w in seen] == sorted(v for v, _w in seen)
    assert sum(sm.verdict_totals().values()) == deposited
    tenants = slo_plane().snapshot()["tenants"].values()
    assert sum(t["windows"]["1h"]["total"] for t in tenants) == deposited
    tl = timeline().query()
    assert sum(s.passed + s.blocked + s.shed + s.other + s.waited
               for s in tl) == deposited


# -- the benchmark's reader of the counter ------------------------------------
def _reader():
    import json
    import os

    from cellbench import manifest

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCHMARK.json")
    with open(bench, encoding="utf-8") as f:
        doc = json.load(f)
    cell = manifest.Cell(bench, doc["workloads"][0]["name"])
    return doc, cell.readers()["service.account_dispatches_per_fold"]


def _stages(folds, dispatches):
    out = {"account_ms": {"count": dispatches, "sum": 0.2 * dispatches,
                          "p50": 0.2, "p99": 0.3}}
    if folds is not None:
        out["account_folds_total"] = folds
    return {"stages": out}


@pytest.mark.parametrize("folds,dispatches,want", [
    (22, 8820, 400.9090909), (100, 100, 1.0), (0, 50, None), (3, 0, None),
    (None, 8820, None),  # a tree from before PR 46: no counter
])
def test_dispatches_per_fold_reads_two_snapshots(folds, dispatches, want):
    _doc, reader = _reader()
    before = _stages(None if folds is None else 5, 40)
    after = _stages(None if folds is None else 5 + folds, 40 + dispatches)
    got = reader.reduce({"before": before, "after": after})
    assert got is None if want is None else got == pytest.approx(want)


def test_the_manifest_entry_agrees_with_the_reader_file():
    doc, reader = _reader()
    by_name = {m["name"]: m for m in doc["per_layer"]}
    m = by_name["service.account_dispatches_per_fold"]
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        m["unit"], m["layer"], m["moves"], m["source"])
    assert m["better"] == "higher"
    assert m["workloads"] == by_name["service.native_prep_share"]["workloads"]
    # appended by PR 46, nothing moved: what later PRs add comes after
    assert doc["per_layer"][62] is m
