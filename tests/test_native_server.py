"""Native epoll front door (``native/src/sentinel_frontdoor.cpp`` +
``cluster/server_native.py``): protocol behavior through real sockets.

Mirrors the asyncio-transport tests (SURVEY §4: service tests with the
transport assumed, plus a socket smoke layer) — same TokenClient drives
both servers, so protocol parity between the two front doors is the test.
"""

import socket
import threading
import time

import numpy as np
import pytest

from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.server_native import (
    NativeTokenServer,
    native_available,
)
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig, TokenStatus
from sentinel_tpu.engine.rules import ThresholdMode

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native library not built"
)

G = ThresholdMode.GLOBAL
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=256)


@pytest.fixture()
def native_server():
    svc = DefaultTokenService(CFG)
    svc.load_rules([
        ClusterFlowRule(flow_id=1, count=5.0, mode=G),
        ClusterFlowRule(flow_id=2, count=1e9, mode=G),
    ])
    server = NativeTokenServer(svc, port=0, idle_ttl_s=None)
    server.start()
    yield server, svc
    server.stop()


class TestNativeFrontdoor:
    def test_ping_batch_single_roundtrip(self, native_server):
        server, svc = native_server
        client = TokenClient("127.0.0.1", server.port, timeout_ms=3000)
        try:
            assert client.ping()
            assert server.connections.connected_count("default") == 1
            out = client.request_batch_arrays(np.full(20, 1, np.int64))
            assert out is not None
            assert int((out[0] == int(TokenStatus.OK)).sum()) == 5
            assert int((out[0] == int(TokenStatus.BLOCKED)).sum()) == 15
            assert all(client.request_token(2).ok for _ in range(5))
            assert (
                client.request_token(999).status
                == TokenStatus.NO_RULE_EXISTS
            )
        finally:
            client.close()

    def test_multi_frame_pipelined_batch(self, native_server):
        # a batch larger than one frame pipelines chunk frames; verdict
        # order must match request order across the chunks
        server, svc = native_server
        client = TokenClient("127.0.0.1", server.port, timeout_ms=5000)
        try:
            n = 12_000  # > MAX_BATCH_PER_FRAME (5040)
            ids = np.full(n, 2, np.int64)
            out = client.request_batch_arrays(ids)
            assert out is not None
            assert int((out[0] == int(TokenStatus.OK)).sum()) == n
        finally:
            client.close()

    def test_concurrent_clients_share_budget(self, native_server):
        server, svc = native_server
        results = []
        lock = threading.Lock()

        def worker():
            client = TokenClient("127.0.0.1", server.port, timeout_ms=3000)
            try:
                mine = [client.request_token(1) for _ in range(4)]
                with lock:
                    results.extend(mine)
            finally:
                client.close()

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sum(r.ok for r in results) == 5
        assert len(results) == 16

    def test_malformed_frame_closes_connection(self, native_server):
        server, svc = native_server
        sock = socket.create_connection(("127.0.0.1", server.port), timeout=3)
        try:
            sock.sendall(b"\x00\x01\xff")  # runt frame (len 1 < header)
            sock.settimeout(3)
            assert sock.recv(64) == b""  # server closed on us
        finally:
            sock.close()

    def test_empty_batch_frame_answered_not_stranded(self, native_server):
        # n=0 BATCH_FLOW adds no requests, so wait_batch never wakes for
        # it — the front door must answer inline instead of queueing a
        # zero-row frame forever (and must keep the connection serviceable)
        server, svc = native_server
        import struct

        sock = socket.create_connection(("127.0.0.1", server.port), timeout=3)
        try:
            payload = struct.pack(">IBH", 42, 5, 0)  # xid=42, BATCH_FLOW, n=0
            sock.sendall(struct.pack(">H", len(payload)) + payload)
            sock.settimeout(3)
            rsp = sock.recv(64)
            assert rsp == struct.pack(">H", 7) + struct.pack(">IBH", 42, 5, 0)
            # connection still alive: a real request round-trips
            row = struct.pack(">qiB", 2, 1, 0)
            payload = struct.pack(">IBH", 43, 5, 1) + row
            sock.sendall(struct.pack(">H", len(payload)) + payload)
            rsp = sock.recv(64)
            (ln,) = struct.unpack(">H", rsp[:2])
            xid, typ, n = struct.unpack(">IBH", rsp[2:9])
            assert (ln, xid, typ, n) == (16, 43, 5, 1)
            assert rsp[9] == int(TokenStatus.OK)
        finally:
            sock.close()

    def test_close_event_deflates_connected_count(self, native_server):
        server, svc = native_server
        client = TokenClient("127.0.0.1", server.port, timeout_ms=3000)
        assert client.ping()
        assert server.connections.connected_count("default") == 1
        client.close()
        deadline = time.time() + 3
        while time.time() < deadline:
            if server.connections.connected_count("default") == 0:
                break
            time.sleep(0.02)
        assert server.connections.connected_count("default") == 0

    def test_concurrent_mode_over_native_control_path(self, native_server):
        from sentinel_tpu.cluster.concurrent import ConcurrentFlowRule

        server, svc = native_server
        svc.load_concurrent_rules(
            [ConcurrentFlowRule(flow_id=9, concurrency_level=2)]
        )
        client = TokenClient("127.0.0.1", server.port, timeout_ms=3000)
        try:
            a = client.request_concurrent_token(9)
            b = client.request_concurrent_token(9)
            c = client.request_concurrent_token(9)
            assert a.ok and b.ok and not c.ok
            r = client.release_concurrent_token(a.token_id)
            assert r.status == TokenStatus.RELEASE_OK
            assert client.request_concurrent_token(9).ok
        finally:
            client.close()

    def test_tuning_kwargs_roundtrip(self, native_server):
        server, svc = native_server
        kw = server.tuning_kwargs()
        assert kw["max_batch"] == server.max_batch
        assert kw["n_dispatchers"] == server.n_dispatchers

    def test_arena_backpressure_small_cap(self):
        # an arena smaller than the offered load parks connections and
        # resumes them after each swap — nothing is lost or reordered.
        # arena_cap=1 clamps to one max frame (5040 rows), so concurrent
        # 5000-row frames from several clients force parking.
        svc = DefaultTokenService(CFG)
        # raise the namespace self-protection guard: this test pushes 45k
        # requests through one namespace in well under a second
        svc.load_rules([ClusterFlowRule(flow_id=2, count=1e9, mode=G)],
                       ns_max_qps=1e12)
        server = NativeTokenServer(svc, port=0, idle_ttl_s=None,
                                   arena_cap=1)
        server.start()
        errors = []

        def worker():
            client = TokenClient("127.0.0.1", server.port, timeout_ms=8000)
            try:
                for _ in range(3):
                    out = client.request_batch_arrays(
                        np.full(5000, 2, np.int64)
                    )
                    if out is None:
                        errors.append("timeout")
                    elif int((out[0] == int(TokenStatus.OK)).sum()) != 5000:
                        errors.append("bad verdicts")
            finally:
                client.close()

        threads = [threading.Thread(target=worker) for _ in range(3)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert errors == []
        finally:
            server.stop()

    def test_restart_clears_phantom_connections(self, native_server):
        # stop() closes sockets natively (no CTRL_CLOSE events), so it must
        # deregister clients itself — a restart inheriting phantom entries
        # would deflate AVG_LOCAL per-connection budgets forever
        server, svc = native_server
        client = TokenClient("127.0.0.1", server.port, timeout_ms=3000)
        try:
            assert client.ping()
            assert server.connections.connected_count("default") == 1
        finally:
            client.close()
        server.stop()
        assert server.connections.connected_count("default") == 0
        server.start()  # fixture's stop() after yield is a no-op re-stop
        assert server.connections.connected_count("default") == 0

    def test_control_queue_backpressure_parks_and_resumes(self):
        # a peer streaming control frames faster than the host drains must
        # park (bounded queue), then resume once the host drains below half
        # — every frame still arrives, none dropped. Uses the raw Frontdoor
        # (no control thread) so the queue actually fills.
        import struct

        from sentinel_tpu.native.lib import Frontdoor

        door = Frontdoor(port=0)
        try:
            sock = socket.create_connection(("127.0.0.1", door.port),
                                            timeout=5)
            sock.settimeout(5)
            n_sent = 10_000  # > kMaxControls (8192)
            # a bare PING: PARAM_FLOW (type 2) is data plane since PR 45,
            # and a frame of it this short closes its connection
            frame = struct.pack(">H", 5) + struct.pack(">IB", 7, 0)
            blob = frame * n_sent
            sender = threading.Thread(
                target=sock.sendall, args=(blob,), daemon=True
            )
            sender.start()
            got = 0
            deadline = time.monotonic() + 30
            while got < n_sent and time.monotonic() < deadline:
                ev = door.next_control()
                if ev is None:
                    time.sleep(0.001)
                    continue
                if ev[0] == 0:  # control frame (skip open/close events)
                    got += 1
            sender.join(timeout=5)
            sock.close()
            assert got == n_sent
        finally:
            door.stop()

    def test_native_idle_sweep_closes_quiet_connection(self):
        svc = DefaultTokenService(CFG)
        svc.load_rules([ClusterFlowRule(flow_id=2, count=1e9, mode=G)])
        server = NativeTokenServer(svc, port=0, idle_ttl_s=0.3)
        server.start()
        client = TokenClient("127.0.0.1", server.port, timeout_ms=3000)
        try:
            assert client.ping()
            assert server.connections.connected_count("default") == 1
            deadline = time.time() + 5  # sweep ticks at 1s
            while time.time() < deadline:
                if server.connections.connected_count("default") == 0:
                    break
                time.sleep(0.1)
            assert server.connections.connected_count("default") == 0
        finally:
            client.close()
            server.stop()


class TestFrontdoorFuzz:
    """Byte-level decoder fuzz (the ``LengthFieldBasedFrameDecoder``
    robustness contract, ``NettyTransportServer.java:80``): hostile bytes
    may close their own connection, never the server. The same corpus runs
    under AddressSanitizer via ``make -C native asan-check``."""

    def test_decoder_survives_hostile_bytes(self):
        import os
        import sys

        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "native")
        )
        from fuzz_frontdoor import run_fuzz

        out = run_fuzz(iters=60, seed=1234, oracle_every=5)
        assert out["oracle_checks"] >= 13

    def test_decoder_survives_hostile_bytes_at_arena_boundary(self):
        import os
        import sys

        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "native")
        )
        from fuzz_frontdoor import run_fuzz

        # cap smaller than one max mutated batch: parse must park/resume
        # around arena-full mid-hostility without wedging
        out = run_fuzz(iters=40, seed=99, arena_cap=16, oracle_every=5)
        assert out["oracle_checks"] >= 9


class TestNativeMixedSoak:
    def test_mixed_planes_under_reload(self, native_server):
        """BATCH_FLOW, single PARAM_FLOW and single CONCURRENT
        acquire/release (data plane all, since PR 45), interleaved over several
        connections while rules reload continuously: the arena, control
        queue, pipelined dispatch, and rules mutex must never hand back a
        non-OK verdict for the always-loaded rules, raise, or wedge a
        client. (The interaction spot the per-plane tests can't reach.)"""
        from sentinel_tpu.cluster.concurrent import ConcurrentFlowRule
        from sentinel_tpu.cluster.token_service import ClusterParamFlowRule

        server, svc = native_server
        # lift the namespace guard out of the way: the zero-copy host path
        # serves a pipelined pump well past the 30k-QPS default, and this
        # soak asserts the always-loaded RULES never block — the ns cap
        # has its own tests
        svc.load_rules([
            ClusterFlowRule(flow_id=1, count=5.0, mode=G),
            ClusterFlowRule(flow_id=2, count=1e9, mode=G),
        ], ns_max_qps=1e12)
        svc.load_param_rules([ClusterParamFlowRule(flow_id=3, count=1e9)])
        # timeout far above the soak duration: a descheduled holder must
        # not have its token swept mid-test (that would be a flake, and
        # the final now_calls assertion covers leaks anyway)
        svc.load_concurrent_rules(
            [ConcurrentFlowRule(flow_id=9, concurrency_level=8,
                                resource_timeout_ms=60_000)]
        )
        stop = threading.Event()
        failures = []

        def guarded(body):
            def run():
                c = TokenClient("127.0.0.1", server.port, timeout_ms=5000)
                try:
                    body(c)
                except Exception as e:  # a raise IS a soak failure
                    failures.append(f"{type(e).__name__}: {e}")
                finally:
                    c.close()
            return run

        @guarded
        def flow_pump(c):
            ids = np.full(32, 2, np.int64)  # flow 2: count 1e9, always loaded
            while not stop.is_set():
                out = c.request_batch_arrays(ids)
                if out is None:
                    failures.append("flow timeout")
                    return
                if (out[0] != int(TokenStatus.OK)).any():
                    failures.append(
                        f"flow non-OK statuses {set(out[0].tolist())}"
                    )
                    return

        @guarded
        def param_pump(c):
            k = 0
            while not stop.is_set():
                k += 1
                r = c.request_params_token(3, 1, [k % 50, 7])
                if int(r.status) != int(TokenStatus.OK):
                    failures.append(f"param status {r.status}")
                    return

        @guarded
        def conc_pump(c):
            while not stop.is_set():
                r = c.request_concurrent_token(9)
                if r.ok and r.token_id:
                    rel = c.release_concurrent_token(r.token_id)
                    if not rel.ok:
                        failures.append(f"release status {rel.status}")
                        return
                elif int(r.status) == int(TokenStatus.FAIL):
                    failures.append("concurrent FAIL")
                    return

        # daemon: a wedged pump must FAIL the test (the is_alive assert),
        # not hang interpreter shutdown joining a non-daemon thread forever
        threads = [
            threading.Thread(target=flow_pump, daemon=True),
            threading.Thread(target=flow_pump, daemon=True),
            threading.Thread(target=param_pump, daemon=True),
            threading.Thread(target=conc_pump, daemon=True),
        ]
        for t in threads:
            t.start()
        for i in range(20):  # continuous reloads against live traffic
            svc.load_rules([
                ClusterFlowRule(flow_id=1, count=5.0, mode=G),
                ClusterFlowRule(flow_id=2, count=1e9, mode=G),
                ClusterFlowRule(flow_id=50 + i, count=1.0, mode=G),
            ])
            time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert [t for t in threads if t.is_alive()] == []  # no wedged pump
        assert failures == []
        # the namespace guard must not have been the limiter: the pinned
        # cap survives every reload above (load_rules only overwrites
        # ns_max_qps when passed explicitly), both in the host config and
        # in the live device table the decide step actually reads. If
        # either drifted back toward the 30k default, the pump would block
        # on the guard and this soak would be testing the wrong thing.
        assert svc._ns_max_qps == 1e12
        # the device table stores float32, so compare in float32
        assert np.asarray(svc._table.ns_max_qps).min() == np.float32(1e12)
        # semaphore fully released after the soak
        assert svc.concurrent_stats()["held"].get(9, 0) == 0
        # freelist quiescence: every staging block acquired on the soak's
        # shed/deadline/reply paths came back to the pool. Once the lanes
        # drain, outstanding must equal exactly the one block each intake
        # lane holds while idle — anything above is a leaked block
        pool = server._staging
        n_lanes = len(server._shard_qs)
        deadline = time.monotonic() + 5.0
        while pool.outstanding > n_lanes and time.monotonic() < deadline:
            time.sleep(0.02)  # in-flight replies still releasing
        assert pool.outstanding == n_lanes, (
            f"staging leak: {pool.outstanding} outstanding, "
            f"{n_lanes} intake lanes (built={pool.built}, "
            f"reused={pool.reused})"
        )


class TestDeviceLanePipelining:
    """Double-buffered device lane: the ``max_device_inflight`` permit
    bound, permit release discipline on every exit path, and the
    overlap/inflight observability surface."""

    def _server(self, **kw):
        svc = DefaultTokenService(CFG)
        svc.load_rules([ClusterFlowRule(flow_id=1, count=1e9, mode=G)])
        return NativeTokenServer(svc, port=0, idle_ttl_s=None, **kw), svc

    def test_tracked_dispatch_permit_lifecycle(self):
        server, _ = self._server(max_device_inflight=2)
        calls = []

        def fake_dispatch(ids, counts, prios):
            calls.append(len(ids))
            return lambda: ("status", "remaining", "wait")

        ids = np.array([1], np.int64)
        cnt = np.array([1], np.int32)
        pri = np.array([False], bool)
        mat1, rel1, ov1 = server._tracked_dispatch(
            fake_dispatch, ids, cnt, pri
        )
        assert server._device_inflight == 1 and ov1 is False
        mat2, rel2, ov2 = server._tracked_dispatch(
            fake_dispatch, ids, cnt, pri
        )
        # second group dispatched while the first is in flight
        assert server._device_inflight == 2 and ov2 is True
        assert mat1() == ("status", "remaining", "wait")
        assert server._device_inflight == 1
        rel1()  # idempotent with mat1's own release
        assert server._device_inflight == 1
        rel2()  # abandon-path escape hatch, mat2 never materialized
        assert server._device_inflight == 0
        mat2()
        assert server._device_inflight == 0
        assert calls == [1, 1]

    def test_tracked_dispatch_releases_on_dispatch_error(self):
        server, _ = self._server(max_device_inflight=2)

        def boom(ids, counts, prios):
            raise RuntimeError("device fell over")

        with pytest.raises(RuntimeError):
            server._tracked_dispatch(
                boom, np.array([1], np.int64),
                np.array([1], np.int32), np.array([False], bool),
            )
        assert server._device_inflight == 0

    def test_inflight_bound_blocks_third_dispatch(self):
        server, _ = self._server(max_device_inflight=1)
        mat1, rel1, _ = server._tracked_dispatch(
            lambda *a: (lambda: None),
            np.array([1], np.int64), np.array([1], np.int32),
            np.array([False], bool),
        )
        entered = threading.Event()
        done = threading.Event()

        def second():
            entered.set()
            server._tracked_dispatch(
                lambda *a: (lambda: None),
                np.array([1], np.int64), np.array([1], np.int32),
                np.array([False], bool),
            )[1]()  # release immediately once admitted
            done.set()

        t = threading.Thread(target=second, daemon=True)
        t.start()
        assert entered.wait(2.0)
        # permit wait holds the second dispatch while the first is live
        assert not done.wait(0.4)
        rel1()
        assert done.wait(2.0), "release must unblock the waiting dispatch"
        t.join(timeout=2.0)
        assert server._device_inflight == 0

    def test_overlap_surface_and_gauge_drain(self):
        from sentinel_tpu.metrics.server import server_metrics

        svc = DefaultTokenService(CFG)
        svc.load_rules([ClusterFlowRule(flow_id=1, count=1e9, mode=G)])
        server = NativeTokenServer(svc, port=0, idle_ttl_s=None)
        server.start()
        try:
            client = TokenClient("127.0.0.1", server.port)
            try:
                for _ in range(30):
                    client.request_batch([(1, 1, False)] * 32)
            finally:
                client.close()
            snap = server_metrics().snapshot()
            assert "overlapSavedMsTotal" in snap
            assert snap["overlapSavedMsTotal"] >= 0.0
            assert "device_inflight" in snap["gauges"]
            text = server_metrics().render()
            assert "sentinel_server_overlap_saved_ms_total" in text
            assert "sentinel_server_device_inflight" in text
        finally:
            server.stop()
        # every permit taken on the traffic above was released
        assert server._device_inflight == 0
