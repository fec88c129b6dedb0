"""Server-side overload protection: deadline-aware admission + shedding.

Covers the wire-codec deadline rev, the BBR admission controller in
isolation, the brownout ladder wired through both front doors (forced
levels via a fake controller), the queue-full OVERLOAD answer, the
deadline shed, failover's OVERLOAD-is-alive contract, the shed metrics
surface, and stop() under sustained load with full queues.
"""

import socket
import threading
import time

import numpy as np
import pytest

from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.server import TokenServer
from sentinel_tpu.cluster.server_native import (
    NativeTokenServer,
    native_available,
)
from sentinel_tpu.cluster.token_service import DefaultTokenService, TokenResult
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig, TokenStatus
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.ha.failover import FailoverTokenClient
from sentinel_tpu.metrics.ha import ha_metrics
from sentinel_tpu.metrics.server import ServerMetrics, server_metrics
from sentinel_tpu.overload import (
    AdmissionController,
    BrownoutLevel,
    OverloadConfig,
)

G = ThresholdMode.GLOBAL
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=256)
_OVL = int(TokenStatus.OVERLOAD)


def _service(count=1e9):
    svc = DefaultTokenService(CFG)
    svc.load_rules([ClusterFlowRule(flow_id=1, count=count, mode=G)])
    return svc


@pytest.fixture(scope="module")
def module_svc():
    # one service (= one decide-kernel compile) for every server test in
    # this module; each test builds its own front door around it
    return _service()


@pytest.fixture
def svc(module_svc):
    """The shared service, with dispatch wrapper + rules restored after
    each test (tests stop their servers before teardown runs)."""
    orig = module_svc.dispatch_batch_arrays
    yield module_svc
    module_svc.dispatch_batch_arrays = orig
    module_svc.load_rules([ClusterFlowRule(flow_id=1, count=1e9, mode=G)])


def _slow_materialize(svc, delay_s):
    """Wrap the service's dispatch so materialization (the device wait,
    which the asyncio loop offloads to a thread) takes ``delay_s``."""
    orig = svc.dispatch_batch_arrays

    def slow_dispatch(ids, counts, prios):
        mat = orig(ids, counts, prios)

        def slow_mat():
            time.sleep(delay_s)
            return mat()

        return slow_mat

    svc.dispatch_batch_arrays = slow_dispatch


class _FakeController(AdmissionController):
    """Pinned brownout level — tests the wiring, not the estimator."""

    def __init__(self, lvl, admit_frac=1.0):
        super().__init__(config=OverloadConfig(), metrics=ServerMetrics())
        self._forced = lvl
        self._admit_frac = admit_frac

    def level(self, now=None):
        return self._forced


# -- codec rev: optional deadline trailer -----------------------------------
class TestDeadlineCodec:
    def test_deadline_roundtrip(self):
        ids = np.array([1, 2, 3], np.int64)
        payload = P.encode_batch_request(7, ids, deadline_ms=1234)[2:]
        xid, got_ids, counts, prios = P.decode_batch_request(payload)
        assert xid == 7 and got_ids.tolist() == [1, 2, 3]
        assert P.decode_batch_deadline(payload) == 1234

    def test_legacy_frame_reads_zero(self):
        payload = P.encode_batch_request(9, np.array([5], np.int64))[2:]
        assert P.decode_batch_deadline(payload) == 0

    def test_deadline_saturates_at_uint32(self):
        payload = P.encode_batch_request(
            1, np.array([1], np.int64), deadline_ms=2**40
        )[2:]
        assert P.decode_batch_deadline(payload) == 0xFFFFFFFF

    def test_trailer_invisible_to_row_decode(self):
        # rev-1 decoders read n rows and ignore trailing bytes — the
        # back-compat contract the rev relies on
        ids = np.arange(10, dtype=np.int64)
        with_dl = P.encode_batch_request(3, ids, deadline_ms=500)[2:]
        without = P.encode_batch_request(3, ids)[2:]
        a = P.decode_batch_request(with_dl)
        b = P.decode_batch_request(without)
        assert a[0] == b[0]
        for x, y in zip(a[1:], b[1:]):
            assert np.array_equal(x, y)


# -- the admission controller in isolation ----------------------------------
class TestAdmissionController:
    def test_inflight_accounting_clamps(self):
        ctl = AdmissionController(
            config=OverloadConfig(), metrics=ServerMetrics()
        )
        ctl.note_enqueued(5)
        assert ctl.inflight == 5
        ctl.note_done(3)
        assert ctl.inflight == 2
        ctl.note_done(10)  # lost accounting must not go negative
        assert ctl.inflight == 0

    def test_level_ladder(self):
        cfg = OverloadConfig(
            headroom_shed=2.0, headroom_degrade=4.0, min_bdp=10.0,
            recheck_ms=0.0, sustain_ms=0.0,
        )
        ctl = AdmissionController(config=cfg, metrics=ServerMetrics())
        # idle metrics → BDP == min_bdp == 10
        assert ctl.level() == BrownoutLevel.NORMAL
        ctl.note_enqueued(21)  # > 2 × 10
        assert ctl.level() == BrownoutLevel.SHED_LOW
        ctl.note_enqueued(20)  # 41 > 4 × 10
        assert ctl.level() == BrownoutLevel.DEGRADE
        ctl.note_done(41)
        assert ctl.level() == BrownoutLevel.NORMAL

    def test_escalation_requires_sustained_pressure(self):
        cfg = OverloadConfig(
            headroom_shed=2.0, headroom_degrade=4.0, min_bdp=10.0,
            recheck_ms=0.0, sustain_ms=40.0,
        )
        ctl = AdmissionController(config=cfg, metrics=ServerMetrics())
        ctl.note_enqueued(100)
        # a fresh spike is NOT overload — a draining burst looks identical
        assert ctl.level() == BrownoutLevel.NORMAL
        time.sleep(0.06)
        assert ctl.level() == BrownoutLevel.DEGRADE
        # a dip below threshold resets the sustain clock
        ctl.note_done(100)
        assert ctl.level() == BrownoutLevel.NORMAL
        ctl.note_enqueued(100)
        assert ctl.level() == BrownoutLevel.NORMAL

    def test_a_burst_that_drained_between_two_looks_is_not_sustained(self):
        """Evaluations come with dispatches. A burst seen over the headroom
        once, drained with nothing looking (the pause between a warm-up and
        a measured window), and a second spike long after: the second is a
        fresh spike, not half a second of pressure. (PR 41's cell lost its
        window's first 7 frames to SHED_LOW this way in 2 of 36 windows: the
        session's held-up releases make its first pull a large one.)"""
        cfg = OverloadConfig(
            headroom_shed=2.0, headroom_degrade=4.0, min_bdp=10.0,
            recheck_ms=0.0, sustain_ms=40.0,
        )
        ctl = AdmissionController(config=cfg, metrics=ServerMetrics())
        ctl.note_enqueued(100)
        assert ctl.level() == BrownoutLevel.NORMAL  # seen high once
        ctl.note_done(100)  # drained; nobody looked
        time.sleep(0.06)
        ctl.note_enqueued(30)
        assert ctl.level() == BrownoutLevel.NORMAL  # a fresh spike
        # and from here pressure that stays is still shed
        time.sleep(0.06)
        assert ctl.level() == BrownoutLevel.SHED_LOW
        # a dip that no evaluation saw restarts the clock as well
        ctl.note_done(25)
        ctl.note_enqueued(25)
        assert ctl.level() == BrownoutLevel.NORMAL

    def test_disabled_never_sheds(self):
        cfg = OverloadConfig(enabled=False, min_bdp=1.0)
        ctl = AdmissionController(config=cfg, metrics=ServerMetrics())
        ctl.note_enqueued(10**6)
        assert ctl.level() == BrownoutLevel.NORMAL

    def test_shed_mask_shed_low_spares_prioritized(self):
        ctl = AdmissionController(
            config=OverloadConfig(), metrics=ServerMetrics()
        )
        prios = np.array([True, False, True, False])
        mask = ctl.shed_mask(prios, BrownoutLevel.SHED_LOW)
        assert mask.tolist() == [False, True, False, True]

    def test_degrade_verdicts_split(self):
        ctl = AdmissionController(
            config=OverloadConfig(retry_hint_ms=7), metrics=ServerMetrics()
        )
        shed = np.array([True, False, True])
        status, remaining, wait = ctl.degrade_verdicts(shed)
        assert status.tolist() == [_OVL, int(TokenStatus.OK), _OVL]
        assert wait.tolist() == [7, 0, 7]
        assert remaining.tolist() == [0, 0, 0]

    def test_degrade_mask_seeded_fraction(self):
        ctl = AdmissionController(
            config=OverloadConfig(), metrics=ServerMetrics(), seed=42
        )
        ctl._admit_frac = 0.5
        mask = ctl.shed_mask(np.zeros(2000, bool), BrownoutLevel.DEGRADE)
        frac_shed = mask.mean()
        assert 0.4 < frac_shed < 0.6  # sheds ~1 - admit_frac

    def test_snapshot_surface(self):
        ctl = AdmissionController(
            config=OverloadConfig(), metrics=ServerMetrics()
        )
        snap = ctl.snapshot()
        assert snap["levelName"] == "NORMAL"
        assert snap["inflight"] == 0 and snap["enabled"] is True


class TestWeightedShed:
    """Per-namespace share-weighted SHED_LOW: each tenant keeps a
    guaranteed ceil(share x N) rows of the batch; only its newest
    non-prioritized rows beyond that are shed."""

    @staticmethod
    def _ctl(shares, default=0.0):
        return AdmissionController(
            config=OverloadConfig(ns_shares=shares,
                                  ns_default_share=default),
            metrics=ServerMetrics(),
        )

    def test_flooding_tenant_sheds_beyond_share(self):
        ctl = self._ctl({"a": 0.25, "b": 0.25})
        # batch of 8: a floods with 6 rows, b sends 2
        ns_idx = np.array([0, 0, 0, 0, 0, 0, 1, 1], np.int32)
        prios = np.zeros(8, bool)
        mask = ctl.shed_mask(prios, BrownoutLevel.SHED_LOW,
                             ns_idx=ns_idx, ns_names=("a", "b"))
        # a's guarantee is ceil(0.25*8)=2: its 4 NEWEST rows are shed
        assert mask.tolist() == [False, False, True, True,
                                 True, True, False, False]

    def test_in_share_tenant_is_untouched(self):
        ctl = self._ctl({"a": 0.5, "b": 0.5})
        ns_idx = np.array([0, 0, 1, 1], np.int32)
        mask = ctl.shed_mask(np.zeros(4, bool), BrownoutLevel.SHED_LOW,
                             ns_idx=ns_idx, ns_names=("a", "b"))
        assert not mask.any()

    def test_prioritized_rows_never_shed_at_shed_low(self):
        ctl = self._ctl({"a": 0.0})
        ns_idx = np.zeros(4, np.int32)
        prios = np.array([True, True, True, False])
        mask = ctl.shed_mask(prios, BrownoutLevel.SHED_LOW,
                             ns_idx=ns_idx, ns_names=("a",))
        # only the single non-prioritized row is sheddable
        assert mask.tolist() == [False, False, False, True]

    def test_unattributed_rows_get_default_share(self):
        ctl = self._ctl({"a": 1.0}, default=0.0)
        # ns_idx -1 = no rule matched: with default share 0, all shed
        ns_idx = np.array([-1, -1, 0, 0], np.int32)
        mask = ctl.shed_mask(np.zeros(4, bool), BrownoutLevel.SHED_LOW,
                             ns_idx=ns_idx, ns_names=("a",))
        assert mask.tolist() == [True, True, False, False]

    def test_no_shares_falls_back_to_legacy(self):
        ctl = AdmissionController(
            config=OverloadConfig(), metrics=ServerMetrics())
        prios = np.array([True, False])
        mask = ctl.shed_mask(prios, BrownoutLevel.SHED_LOW,
                             ns_idx=np.zeros(2, np.int32), ns_names=("a",))
        assert mask.tolist() == [False, True]  # ~prios, as before

    def test_no_attribution_falls_back_to_legacy(self):
        ctl = self._ctl({"a": 1.0})
        prios = np.array([True, False])
        assert ctl.shed_mask(prios, BrownoutLevel.SHED_LOW).tolist() == [
            False, True]

    def test_set_shares_installs_and_clears(self):
        ctl = AdmissionController(
            config=OverloadConfig(), metrics=ServerMetrics())
        ctl.set_shares({"a": 0.5})
        assert ctl.snapshot()["nsShares"] == {"a": 0.5}
        ctl.set_shares(None)
        assert ctl.snapshot()["nsShares"] == {}

    def test_parse_shares(self):
        from sentinel_tpu.overload import parse_shares

        assert parse_shares("a=0.25, b=0.5") == {"a": 0.25, "b": 0.5}
        assert parse_shares("") == {}
        # malformed entries are dropped, negatives clamped to 0
        assert parse_shares("a=x,b=-1,=0.2,c=0.1") == {"b": 0.0, "c": 0.1}


# -- shed metrics surface ----------------------------------------------------
class TestShedMetrics:
    def test_count_and_render(self):
        m = ServerMetrics()
        m.count_shed("queue_full", 3)
        m.count_shed("deadline", 2)
        m.count_shed("deadline", -5)  # ignored
        assert m.shed_totals() == {"queue_full": 3, "deadline": 2}
        assert m.shed_total == 5
        text = m.render()
        assert 'sentinel_server_shed_total{reason="queue_full"} 3' in text
        assert 'sentinel_server_shed_total{reason="deadline"} 2' in text
        snap = m.snapshot()
        assert snap["shedTotal"] == 5
        assert snap["shedByReason"]["queue_full"] == 3

    def test_zero_sample_always_rendered(self):
        m = ServerMetrics()
        assert 'sentinel_server_shed_total{reason="queue_full"} 0' in m.render()


# -- asyncio front door: queue-full OVERLOAD + deadline shed ----------------
class TestAsyncioOverload:
    def test_queue_full_answers_overload(self, svc):
        _slow_materialize(svc, 0.15)
        server = TokenServer(
            svc, port=0, max_queue=1, max_inflight=1, max_batch=8,
            inline_below=0, batch_window_ms=0.0,
        )
        server.start()
        shed0 = server_metrics().shed_totals().get("queue_full", 0)
        results = [None] * 6
        try:
            def worker(i):
                c = TokenClient("127.0.0.1", server.port, timeout_ms=4000)
                try:
                    results[i] = c.request_batch_arrays(
                        np.full(8, 1, np.int64)
                    )
                finally:
                    c.close()

            threads = [
                threading.Thread(target=worker, args=(i,)) for i in range(6)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
        finally:
            server.stop()
        assert all(r is not None for r in results), "every request answered"
        all_status = np.concatenate([r[0] for r in results])
        assert (all_status == _OVL).sum() > 0, "some rows refused"
        assert server_metrics().shed_totals().get("queue_full", 0) > shed0
        # refused rows carry the retry hint
        hinted = np.concatenate([r[2] for r in results])[all_status == _OVL]
        assert (hinted == server.overload.retry_hint_ms).all()

    def test_expired_deadline_is_dropped_not_served(self, svc):
        _slow_materialize(svc, 0.25)
        server = TokenServer(
            svc, port=0, max_inflight=1, max_batch=8, inline_below=0,
            batch_window_ms=0.0,
        )
        server.start()
        shed0 = server_metrics().shed_totals().get("deadline", 0)
        try:
            s = socket.create_connection(("127.0.0.1", server.port), 3)
            s.settimeout(3.0)
            # frame A occupies the device for 300ms…
            s.sendall(P.encode_batch_request(1, np.array([1], np.int64)))
            time.sleep(0.1)  # let A get picked up
            # …frame B's 50ms budget expires while it waits in the queue
            s.sendall(
                P.encode_batch_request(
                    2, np.full(8, 1, np.int64), deadline_ms=50
                )
            )
            buf = b""
            xids = set()
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline and len(xids) < 1:
                try:
                    chunk = s.recv(65536)
                except socket.timeout:
                    break
                if not chunk:
                    break
                buf += chunk
                fr = P.FrameReader()
                for payload in fr.feed(buf):
                    xids.add(P.decode_batch_response(payload)[0])
            assert 1 in xids, "the live frame is answered"
            # B was shed: counted, and no response frame for xid 2
            assert 2 not in xids
            s.close()
        finally:
            server.stop()
        assert server_metrics().shed_totals().get("deadline", 0) >= shed0 + 8

    def test_shed_low_spares_prioritized_rows(self, svc):
        server = TokenServer(
            svc, port=0, overload=_FakeController(BrownoutLevel.SHED_LOW),
            inline_below=1024,
        )
        server.start()
        try:
            c = TokenClient("127.0.0.1", server.port, timeout_ms=3000)
            prios = np.array([True, False] * 8)
            out = c.request_batch_arrays(
                np.full(16, 1, np.int64), prios=prios
            )
            c.close()
        finally:
            server.stop()
        assert out is not None
        status = out[0]
        assert (status[~prios] == _OVL).all(), "non-prio rows refused"
        assert (status[prios] == int(TokenStatus.OK)).all(), "prio rows served"

    def test_degrade_answers_locally_without_device(self, svc):
        svc.load_rules(  # budget of ONE: device would block most
            [ClusterFlowRule(flow_id=1, count=1.0, mode=G)]
        )
        server = TokenServer(
            svc, port=0,
            overload=_FakeController(BrownoutLevel.DEGRADE, admit_frac=1.0),
        )
        server.start()
        try:
            c = TokenClient("127.0.0.1", server.port, timeout_ms=3000)
            out = c.request_batch_arrays(np.full(10, 1, np.int64))
            c.close()
        finally:
            server.stop()
        assert out is not None
        # every row passed locally — impossible via the device (budget 1),
        # so DEGRADE provably never consulted it
        assert (out[0] == int(TokenStatus.OK)).all()

    def test_stop_under_sustained_load_returns_promptly(self, svc):
        _slow_materialize(svc, 0.15)
        server = TokenServer(
            svc, port=0, max_queue=2, max_inflight=1, max_batch=8,
            inline_below=0,
        )
        server.start()
        stop_evt = threading.Event()

        def hammer():
            c = TokenClient("127.0.0.1", server.port, timeout_ms=300)
            while not stop_evt.is_set():
                c.request_batch_arrays(np.full(8, 1, np.int64))
            c.close()

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.3)  # queues full, device busy
        t0 = time.monotonic()
        server.stop()
        elapsed = time.monotonic() - t0
        stop_evt.set()
        for t in threads:
            t.join(timeout=5)
        assert elapsed < 10.0, f"stop() hung for {elapsed:.1f}s"


# -- native front door -------------------------------------------------------
@pytest.mark.skipif(not native_available(), reason="native library not built")
class TestNativeOverload:
    def test_intake_gives_up_and_answers_overload(self, svc):
        orig = svc.dispatch_batch_arrays

        def slow_dispatch(ids, counts, prios):
            time.sleep(0.15)  # stall the device lane (a thread, not a loop)
            return orig(ids, counts, prios)

        svc.dispatch_batch_arrays = slow_dispatch
        server = NativeTokenServer(
            svc, port=0, fuse_depth=1, n_dispatchers=1, shed_age_ms=100.0,
            idle_ttl_s=None,
        )
        server.start()
        shed0 = server_metrics().shed_totals()
        results = [None] * 6
        try:
            def worker(i):
                c = TokenClient("127.0.0.1", server.port, timeout_ms=6000)
                try:
                    results[i] = c.request_batch_arrays(
                        np.full(16, 1, np.int64)
                    )
                finally:
                    c.close()

            threads = [
                threading.Thread(target=worker, args=(i,)) for i in range(6)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=15)
        finally:
            server.stop()
        assert all(r is not None for r in results), "every request answered"
        all_status = np.concatenate([r[0] for r in results])
        assert (all_status == _OVL).sum() > 0
        shed1 = server_metrics().shed_totals()
        sheds = sum(
            shed1.get(k, 0) - shed0.get(k, 0)
            for k in ("queue_full", "deadline")
        )
        assert sheds > 0

    def test_degrade_wiring(self, svc):
        svc.load_rules([ClusterFlowRule(flow_id=1, count=1.0, mode=G)])
        server = NativeTokenServer(
            svc, port=0,
            overload=_FakeController(BrownoutLevel.DEGRADE, admit_frac=1.0),
            idle_ttl_s=None,
        )
        server.start()
        try:
            c = TokenClient("127.0.0.1", server.port, timeout_ms=3000)
            out = c.request_batch_arrays(np.full(10, 1, np.int64))
            c.close()
        finally:
            server.stop()
        assert out is not None
        assert (out[0] == int(TokenStatus.OK)).all()

    def test_stop_under_sustained_load_respects_drain_timeout(self, svc):
        orig = svc.dispatch_batch_arrays

        def slow_dispatch(ids, counts, prios):
            time.sleep(0.12)
            return orig(ids, counts, prios)

        svc.dispatch_batch_arrays = slow_dispatch
        server = NativeTokenServer(
            svc, port=0, fuse_depth=1, n_dispatchers=1, shed_age_ms=100.0,
            drain_timeout_s=2.0, idle_ttl_s=None,
        )
        server.start()
        stop_evt = threading.Event()

        def hammer():
            c = TokenClient("127.0.0.1", server.port, timeout_ms=300)
            while not stop_evt.is_set():
                c.request_batch_arrays(np.full(8, 1, np.int64))
            c.close()

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        t0 = time.monotonic()
        server.stop()
        elapsed = time.monotonic() - t0
        stop_evt.set()
        for t in threads:
            t.join(timeout=5)
        # lanes get drain_timeout_s each + abandon grace; 4 lanes × 2s
        # bounds well under the hard ceiling
        assert elapsed < 15.0, f"stop() hung for {elapsed:.1f}s"


# -- failover: OVERLOAD is proof of life ------------------------------------
class _StubClient:
    """Per-port scripted endpoint client (failover walk tests)."""

    scripts = {}

    def __init__(self, host, port, timeout_ms=20, namespace="default"):
        self.port = port

    def _answer(self):
        r = self.scripts[self.port]
        return r() if callable(r) else r

    def request_token(self, flow_id, acquire=1, prioritized=False):
        return self._answer()

    def request_batch_arrays(self, flow_ids, acquires=None, prios=None,
                             timeout_ms=None):
        return self._answer()

    def close(self):
        pass


class TestFailoverOverload:
    def _fc(self, scripts):
        _StubClient.scripts = scripts
        return FailoverTokenClient(
            [("a", 1), ("b", 2)], client_factory=_StubClient,
            failure_threshold=3,
        )

    def test_overload_backs_off_to_standby_without_breaker_charge(self):
        fb0 = ha_metrics().fallback_totals().get("overload_backoff", 0)
        fc = self._fc({
            1: TokenResult(TokenStatus.OVERLOAD, wait_ms=5),
            2: TokenResult(TokenStatus.OK, remaining=9),
        })
        for _ in range(10):
            r = fc.request_token(1)
            assert r.status == TokenStatus.OK
        # the overloaded-but-alive primary was never evicted
        snap = fc.health_snapshot()
        assert snap[0]["state"] == "CLOSED"
        assert (
            ha_metrics().fallback_totals().get("overload_backoff", 0)
            >= fb0 + 10
        )

    def test_all_overloaded_returns_overload_not_fallback(self):
        fc = self._fc({
            1: TokenResult(TokenStatus.OVERLOAD, wait_ms=5),
            2: TokenResult(TokenStatus.OVERLOAD, wait_ms=7),
        })
        r = fc.request_token(1)
        # the explicit refusal (with its retry hint) surfaces to the caller
        assert r.status == TokenStatus.OVERLOAD
        assert r.wait_ms == 5
        snap = fc.health_snapshot()
        assert all(e["state"] == "CLOSED" for e in snap)

    def test_fully_overloaded_batch_walks_partial_returns(self):
        ovl = (
            np.full(4, _OVL, np.int8),
            np.zeros(4, np.int32),
            np.full(4, 5, np.int32),
        )
        ok = (
            np.zeros(4, np.int8),
            np.zeros(4, np.int32),
            np.zeros(4, np.int32),
        )
        fc = self._fc({1: ovl, 2: ok})
        st, _, _ = fc.request_batch_arrays(np.full(4, 1, np.int64))
        assert (st == 0).all(), "all-OVERLOAD batch walks to the standby"
        # partial overload is an ANSWER: returned as-is from the primary
        mixed = (
            np.array([0, _OVL, 0, _OVL], np.int8),
            np.zeros(4, np.int32),
            np.zeros(4, np.int32),
        )
        fc2 = self._fc({1: mixed, 2: ok})
        st2, _, _ = fc2.request_batch_arrays(np.full(4, 1, np.int64))
        assert st2.tolist() == [0, _OVL, 0, _OVL]
