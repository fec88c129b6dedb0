"""Fused multi-frame dispatch + three-lane native pipeline (PR 3).

Service layer: fused ``lax.scan`` dispatch must be bit-identical to the
per-frame path, the fusion ladder must adapt its depth to burst size, and
prep must follow a rule reload. Transport layer: the
three-lane native server must answer every xid exactly once through a
drain shutdown, and a lone frame must never sleep out the intake timeout.
"""

import socket
import struct
import threading
import time

import numpy as np
import pytest

from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig, TokenStatus
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.metrics.server import server_metrics

G = ThresholdMode.GLOBAL
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=64)
CAP = CFG.batch_size
_SM = server_metrics()


def _rules(n=8, count=50.0):
    return [
        ClusterFlowRule(flow_id=i, count=count, mode=G)
        for i in range(1, n + 1)
    ]


def _traffic(n, seed=0, mixed=False):
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, 10, size=n).astype(np.int64)  # id 9 has no rule
    acq = (
        rng.integers(1, 3, size=n).astype(np.int32)
        if mixed else np.ones(n, np.int32)
    )
    pr = np.zeros(n, bool)
    return ids, acq, pr


class TestFusedDispatch:
    """Fused-frame results must be indistinguishable from per-frame."""

    @pytest.mark.parametrize("mixed", [False, True])
    def test_fused_bit_identical_to_per_frame(self, manual_clock, mixed):
        svc_f = DefaultTokenService(CFG)  # default ladder (8, 4, 2)
        svc_p = DefaultTokenService(CFG, fuse_depths=())  # per-frame
        for svc in (svc_f, svc_p):
            svc.load_rules(_rules())
        # 6 full frames (fused as scan(4) + scan(2)) + a partial tail,
        # repeated so later windows carry accumulated state
        n = 6 * CAP + 37
        for seed in range(3):
            ids, acq, pr = _traffic(n, seed=seed, mixed=mixed)
            out_f = svc_f.request_batch_arrays(ids, acq, pr)
            out_p = svc_p.request_batch_arrays(ids, acq, pr)
            for a, b, name in zip(out_f, out_p, ("status", "rem", "wait")):
                np.testing.assert_array_equal(a, b, err_msg=name)
            manual_clock.sleep(300)

    def test_fused_depth_adapts_to_burst_size(self, manual_clock):
        svc = DefaultTokenService(CFG)
        svc.load_rules(_rules())
        _SM.reset()
        # sub-cap burst: no full frames, nothing to fuse
        svc.request_batch_arrays(*_traffic(CAP))
        assert _SM.fused_frames_total == 0
        # 3 full frames: ladder (8, 4, 2) takes scan(2) + 1 plain frame
        svc.request_batch_arrays(*_traffic(3 * CAP))
        assert _SM.fused_frames_total == 2
        # 13 full frames: greedy largest-fit → scan(8) + scan(4) + 1 plain
        svc.request_batch_arrays(*_traffic(13 * CAP))
        assert _SM.fused_frames_total == 2 + 8 + 4
        depths = _SM.fused_depth.snapshot()
        assert depths["count"] == 3  # three fused groups issued
        assert depths["max"] == 8.0
        assert _SM.render().count("sentinel_server_fused_frames_total") >= 1

    def test_fusion_disabled_ladder_empty(self, manual_clock):
        svc = DefaultTokenService(CFG, fuse_depths=())
        svc.load_rules(_rules())
        _SM.reset()
        out = svc.request_batch_arrays(*_traffic(8 * CAP))
        assert out[0].shape == (8 * CAP,)
        assert _SM.fused_frames_total == 0

    def test_prep_follows_a_rule_reload(self, manual_clock):
        svc = DefaultTokenService(CFG)
        svc.load_rules(_rules(count=5.0))
        ids = np.full(CAP, 1, np.int64)
        out1 = svc.request_batch_arrays(ids)
        assert int((out1[0] == int(TokenStatus.OK)).sum()) == 5
        manual_clock.sleep(1100)
        svc.load_rules(_rules(count=7.0))  # new lookup snapshot
        out2 = svc.request_batch_arrays(ids)
        assert int((out2[0] == int(TokenStatus.OK)).sum()) == 7


# -- transport layer ---------------------------------------------------------

from sentinel_tpu.cluster.server_native import (  # noqa: E402
    NativeTokenServer,
    native_available,
)

native_only = pytest.mark.skipif(
    not native_available(), reason="native library not built"
)

SRV_CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=256)


def _read_frames(sock, k, timeout=15.0):
    """Read exactly k length-prefixed response frames."""
    sock.settimeout(timeout)
    buf = b""
    frames = []
    while len(frames) < k:
        need = 2 if len(buf) < 2 else 2 + struct.unpack(">H", buf[:2])[0]
        while len(buf) < need:
            chunk = sock.recv(65536)
            if not chunk:
                raise AssertionError(
                    f"connection closed after {len(frames)}/{k} frames"
                )
            buf += chunk
            if len(buf) >= 2:
                need = 2 + struct.unpack(">H", buf[:2])[0]
        frames.append(buf[2:need])
        buf = buf[need:]
    return frames, buf


@native_only
class TestThreeLanePipeline:
    def _server(self, **kw):
        svc = DefaultTokenService(SRV_CFG)
        svc.load_rules(
            [ClusterFlowRule(flow_id=2, count=1e9, mode=G)]
        )
        server = NativeTokenServer(svc, port=0, idle_ttl_s=None, **kw)
        server.start()
        return server

    def test_no_lost_or_double_answered_xids(self):
        """Bursty pipelined enqueue: every xid answered exactly once, in
        per-row request order, through lanes and fused dispatch alike."""
        server = self._server(fuse_depth=4)
        try:
            with socket.create_connection(
                ("127.0.0.1", server.port), timeout=10
            ) as s:
                k, rows = 40, 512
                ids = np.full(rows, 2, np.int64)
                # one blast of K frames so lanes see a deep backlog
                s.sendall(
                    b"".join(
                        P.encode_batch_request(xid, ids)
                        for xid in range(1, k + 1)
                    )
                )
                frames, rest = _read_frames(s, k)
                assert rest == b""
                seen = {}
                for raw in frames:
                    xid, status, _rem, _wait = P.decode_batch_response(raw)
                    seen[xid] = seen.get(xid, 0) + 1
                    assert status.shape == (rows,)
                    assert (status == int(TokenStatus.OK)).all()
                assert seen == {xid: 1 for xid in range(1, k + 1)}
        finally:
            server.stop()

    def test_drain_shutdown_answers_inflight(self):
        """stop() must drain the lanes: frames accepted before the stop
        are answered before the door closes (no lost xids)."""
        server = self._server(fuse_depth=4)
        try:
            with socket.create_connection(
                ("127.0.0.1", server.port), timeout=10
            ) as s:
                k, rows = 24, 1024
                ids = np.full(rows, 2, np.int64)
                s.sendall(
                    b"".join(
                        P.encode_batch_request(xid, ids)
                        for xid in range(1, k + 1)
                    )
                )
                # give intake a moment to pull the backlog, then stop mid-
                # flight: the lanes drain in order before the door closes
                time.sleep(0.15)
                stopper = threading.Thread(target=server.stop)
                stopper.start()
                frames, _ = _read_frames(s, k)
                stopper.join(timeout=30)
                assert not stopper.is_alive()
                xids = sorted(
                    P.decode_batch_response(raw)[0] for raw in frames
                )
                assert xids == list(range(1, k + 1))
        finally:
            server.stop()  # idempotent

    def test_single_frame_never_sleeps_out_timeout(self):
        """The wait_batch stall regression: the door wakes the intake lane
        the moment one frame queues, so a lone request's RTT stays far
        below the intake timeout even when that timeout is huge."""
        server = self._server(intake_timeout_ms=500)
        try:
            with socket.create_connection(
                ("127.0.0.1", server.port), timeout=10
            ) as s:
                # warm the path (first hit may pay compile/cache misses)
                s.sendall(P.encode_batch_request(1, np.full(8, 2, np.int64)))
                _read_frames(s, 1)
                t0 = time.perf_counter()
                s.sendall(P.encode_batch_request(2, np.full(8, 2, np.int64)))
                _read_frames(s, 1)
                rtt = time.perf_counter() - t0
                assert rtt < 0.4, f"single-frame RTT {rtt*1e3:.1f}ms"
        finally:
            server.stop()

    def test_fused_frames_flow_through_native_server(self):
        """Bursty enqueue through the real socket path reaches the fusion
        ladder (fused_frames_total advances) and still answers correctly."""
        _SM.reset()
        server = self._server(fuse_depth=8, n_dispatchers=2)
        try:
            with socket.create_connection(
                ("127.0.0.1", server.port), timeout=10
            ) as s:
                k = 24
                rows = P.MAX_BATCH_PER_FRAME
                ids = np.full(rows, 2, np.int64)
                s.sendall(
                    b"".join(
                        P.encode_batch_request(xid, ids)
                        for xid in range(1, k + 1)
                    )
                )
                frames, _ = _read_frames(s, k)
                assert len(frames) == k
            # k frames × MAX_BATCH rows ≫ batch_size: the device lane's
            # concatenated pulls must have fused full engine frames
            assert _SM.fused_frames_total >= 4
        finally:
            server.stop()


# -- zero-copy host path -----------------------------------------------------

from sentinel_tpu.engine import (  # noqa: E402
    alloc_fused_batch,
    make_batch,
    make_batch_into,
)


class TestZeroCopyDecode:
    """decode_batch_request_into must be bit-identical to the allocating
    decoder — the rows just land in caller-owned staging."""

    def test_decode_into_bit_identical_randomized(self):
        rng = np.random.default_rng(0xD6)
        cap = 4096
        ids_out = np.empty(cap, np.int64)
        counts_out = np.empty(cap, np.int32)
        prios_out = np.empty(cap, bool)
        at = 0
        for trial in range(60):
            n = int(rng.integers(0, 300))
            if at + n > cap:
                at = 0
            ids = rng.integers(-(2**62), 2**62, size=n).astype(np.int64)
            cnt = rng.integers(-(2**31), 2**31 - 1, size=n).astype(np.int32)
            pr = rng.integers(0, 2, size=n).astype(bool)
            xid = int(rng.integers(-(2**31), 2**31 - 1))
            deadline = int(rng.integers(0, 3)) * 17 or None
            payload = P.encode_batch_request(
                xid, ids, cnt, pr, deadline_ms=deadline
            )[2:]
            x_ref, i_ref, c_ref, p_ref = P.decode_batch_request(payload)
            x_new, m = P.decode_batch_request_into(
                payload, ids_out, counts_out, prios_out, at=at
            )
            assert (x_new, m) == (x_ref, n) and x_ref == xid
            np.testing.assert_array_equal(ids_out[at : at + n], i_ref)
            np.testing.assert_array_equal(counts_out[at : at + n], c_ref)
            np.testing.assert_array_equal(prios_out[at : at + n], p_ref)
            at += n

    def test_decode_into_rejects_truncated_and_overflow(self):
        payload = P.encode_batch_request(7, np.arange(10, dtype=np.int64))[2:]
        ids_out = np.empty(64, np.int64)
        counts_out = np.empty(64, np.int32)
        prios_out = np.empty(64, bool)
        with pytest.raises(ValueError, match="truncated"):
            P.decode_batch_request_into(
                payload[:-3], ids_out, counts_out, prios_out
            )
        with pytest.raises(ValueError, match="staging overflow"):
            P.decode_batch_request_into(
                payload, ids_out, counts_out, prios_out, at=60
            )
        # the error paths must not have written past the staging span
        # guard: a rejected frame leaves the arrays usable
        xid, n = P.decode_batch_request_into(
            payload, ids_out, counts_out, prios_out, at=0
        )
        assert (xid, n) == (7, 10)


class TestScatterEncode:
    """encode_batch_responses: uniform fast path, ragged fallback, and the
    out= scatter buffer must all produce identical bytes."""

    def _random_frames(self, rng, uniform):
        F = int(rng.integers(1, 9))
        if uniform:
            counts = np.full(F, int(rng.integers(1, 65)), np.int64)
        else:
            counts = rng.integers(0, 65, size=F).astype(np.int64)
        total = int(counts.sum())
        xids = rng.integers(-(2**31), 2**31 - 1, size=F).astype(np.int64)
        st = rng.integers(-5, 10, size=total).astype(np.int8)
        rm = rng.integers(-(2**31), 2**31 - 1, size=total).astype(np.int32)
        wt = rng.integers(0, 2**31 - 1, size=total).astype(np.int32)
        return xids, counts, st, rm, wt

    def test_scatter_encode_bit_identical_randomized(self):
        rng = np.random.default_rng(0xE7)
        for trial in range(40):
            xids, counts, st, rm, wt = self._random_frames(
                rng, uniform=bool(trial % 2)
            )
            blob = P.encode_batch_responses(xids, counts, st, rm, wt)
            # reference: one single-frame encode per frame, concatenated
            ref = b""
            off = 0
            for f in range(len(xids)):
                n = int(counts[f])
                ref += P.encode_batch_response(
                    int(xids[f]), st[off : off + n], rm[off : off + n],
                    wt[off : off + n],
                )
                off += n
            assert blob == ref
            assert len(blob) == P.batch_responses_size(counts)
            # scatter path: same bytes laid into a reused bytearray
            buf = bytearray()
            mv = P.encode_batch_responses(xids, counts, st, rm, wt, out=buf)
            assert bytes(mv) == ref
            # second encode into the SAME buffer (steady-state reuse)
            mv2 = P.encode_batch_responses(xids, counts, st, rm, wt, out=buf)
            assert bytes(mv2) == ref

    def test_out_buffer_grows_then_steady(self):
        xids = np.array([1, 2], np.int64)
        counts = np.array([3, 3], np.int64)
        st = np.zeros(6, np.int8)
        rm = np.zeros(6, np.int32)
        wt = np.zeros(6, np.int32)
        buf = bytearray(4)  # deliberately too small
        mv = P.encode_batch_responses(xids, counts, st, rm, wt, out=buf)
        assert len(mv) == P.batch_responses_size(counts)
        assert len(buf) >= len(mv)
        cap_after_grow = len(buf)
        P.encode_batch_responses(xids, counts, st, rm, wt, out=buf)
        assert len(buf) == cap_after_grow  # no regrow on reuse


class TestStagingPool:
    def test_reuse_after_release(self):
        made = []

        def factory():
            made.append(object())
            return made[-1]

        pool = P.StagingPool(factory, capacity=2)
        a, b, c = pool.acquire(), pool.acquire(), pool.acquire()
        assert (pool.built, pool.reused) == (3, 0)
        pool.release(a)
        assert pool.acquire() is a  # LIFO recycle, no fresh build
        assert (pool.built, pool.reused) == (3, 1)
        pool.release(None)  # tolerated no-op
        pool.release(a)
        pool.release(b)
        pool.release(c)  # over capacity: dropped, not parked
        assert pool.acquire() in (a, b)
        assert pool.acquire() in (a, b)
        assert pool.built == 3 and pool.reused == 3
        # freelist drained → next acquire builds fresh
        pool.acquire()
        assert pool.built == 4


class TestMakeBatchInto:
    def test_bit_identical_to_make_batch_randomized(self):
        rng = np.random.default_rng(0xF8)
        depth = 3
        block = alloc_fused_batch(CFG, depth)
        for trial in range(30):
            f = int(rng.integers(0, depth))
            n = int(rng.integers(0, CFG.batch_size + 1))
            slots = rng.integers(0, 64, size=n).astype(np.int32)
            acq = rng.integers(1, 5, size=n).astype(np.int32)
            pr = rng.integers(0, 2, size=n).astype(bool)
            if trial % 3 == 0:
                make_batch_into(block, f, slots)
                ref = make_batch(CFG, slots)
            else:
                make_batch_into(block, f, slots, acq, pr)
                ref = make_batch(CFG, slots, acq, pr)
            np.testing.assert_array_equal(block.flow_slot[f], ref.flow_slot)
            np.testing.assert_array_equal(block.acquire[f], ref.acquire)
            np.testing.assert_array_equal(
                block.prioritized[f], ref.prioritized
            )
            np.testing.assert_array_equal(block.valid[f], ref.valid)

    def test_oversized_row_raises(self):
        block = alloc_fused_batch(CFG, 1)
        with pytest.raises(ValueError):
            make_batch_into(
                block, 0, np.zeros(CFG.batch_size + 1, np.int32)
            )


@native_only
class TestShardedIntake:
    """SO_REUSEPORT multi-door intake: N doors on one port, per-shard
    queues, one device lane draining the union."""

    def _server(self, **kw):
        svc = DefaultTokenService(SRV_CFG)
        svc.load_rules([ClusterFlowRule(flow_id=2, count=1e9, mode=G)])
        server = NativeTokenServer(svc, port=0, idle_ttl_s=None, **kw)
        server.start()
        return server

    def test_doors_share_one_port_and_lose_no_xids(self):
        _SM.reset()
        server = self._server(intake_shards=2, fuse_depth=4)
        try:
            assert len(server._doors) == 2
            assert all(d.port == server.port for d in server._doors)
            assert server.tuning_kwargs()["intake_shards"] == 2
            per_client, rows = 25, 128
            ids = np.full(rows, 2, np.int64)

            def run_client(tag, results):
                with socket.create_connection(
                    ("127.0.0.1", server.port), timeout=15
                ) as s:
                    s.sendall(
                        b"".join(
                            P.encode_batch_request(tag * 1000 + i, ids)
                            for i in range(per_client)
                        )
                    )
                    frames, _ = _read_frames(s, per_client)
                    results[tag] = sorted(
                        P.decode_batch_response(raw)[0] for raw in frames
                    )

            # several connections so the kernel's REUSEPORT hash has a
            # chance to spread them across both doors (not guaranteed —
            # correctness must hold either way)
            results = {}
            clients = [
                threading.Thread(target=run_client, args=(t, results))
                for t in range(1, 7)
            ]
            for t in clients:
                t.start()
            for t in clients:
                t.join(timeout=30)
            for tag in range(1, 7):
                assert results[tag] == [
                    tag * 1000 + i for i in range(per_client)
                ]
            # aggregated door stats cover every frame exactly once
            st = server.stats()
            assert st["requests_in"] == 6 * per_client * rows
            shard_rows = sum(
                s["requests"] for s in _SM.shard_totals().values()
            )
            assert shard_rows == 6 * per_client * rows
        finally:
            server.stop()

    def test_staging_blocks_recycle_not_leak(self):
        server = self._server(intake_shards=2, fuse_depth=4)
        try:
            pool = server._staging
            with socket.create_connection(
                ("127.0.0.1", server.port), timeout=15
            ) as s:
                for round_ in range(6):
                    s.sendall(
                        b"".join(
                            P.encode_batch_request(
                                round_ * 10 + i, np.full(256, 2, np.int64)
                            )
                            for i in range(8)
                        )
                    )
                    _read_frames(s, 8)
            # quiesced: every block except the one each intake lane holds
            # must be back on the freelist (a leak would strand blocks)
            expected_free = pool.built - server.intake_shards
            deadline = time.time() + 2.0
            while time.time() < deadline:
                if len(pool._free) == expected_free:
                    break
                time.sleep(0.01)
            assert len(pool._free) == expected_free
            assert pool.reused > 0  # steady state recycles, not reallocs
        finally:
            server.stop()
