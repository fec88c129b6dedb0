"""Front-door fuzz: arbitrary bytes must never kill a serving thread.

Satellite of the overload/chaos PR: both front doors (asyncio and native)
and the client's reader thread receive seeded garbage — truncated frames,
runt frames, bogus lengths, random blobs — and the invariant is graceful
connection drop + continued service, never a dead lane or a wedged loop.
"""

import random
import socket
import struct
import threading

import numpy as np
import pytest

from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.server import TokenServer
from sentinel_tpu.cluster.server_native import (
    NativeTokenServer,
    native_available,
)
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
from sentinel_tpu.engine.rules import ThresholdMode

G = ThresholdMode.GLOBAL
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=256)

SEED = 0xC0FFEE


def _service():
    svc = DefaultTokenService(CFG)
    svc.load_rules([ClusterFlowRule(flow_id=1, count=1e9, mode=G)])
    return svc


@pytest.fixture(scope="module")
def svc():
    # one service (= one decide-kernel compile) shared by both front doors
    return _service()


@pytest.fixture(scope="module")
def asyncio_server(svc):
    server = TokenServer(svc, port=0)
    server.start()
    yield server
    server.stop()


def _garbage_corpus(seed=SEED, n=40):
    """Seeded adversarial byte blobs: random, runt, truncated, bogus-type,
    bogus-length, zero-length — every framing failure class."""
    rng = random.Random(seed)
    corpus = [
        b"\x00\x00",  # zero-length frame
        b"\x00\x02xx",  # runt: payload below header size
        b"\x00\x01\x00",  # one-byte payload
        b"\xff\xff" + b"A" * 10,  # declared 65535, delivered 10 (truncate)
        struct.pack(">H", 9) + struct.pack(">ib", 1, 99) + b"????",  # bad type
        P.encode_request(P.Ping(1))[:-2],  # truncated valid frame
    ]
    for _ in range(n):
        corpus.append(bytes(rng.randrange(256) for _ in range(rng.randrange(1, 200))))
    # a structurally-valid BATCH_FLOW header with a lying row count
    lying = struct.pack(">H", 7) + struct.pack(">ib", 5, int(P.MsgType.BATCH_FLOW)) + struct.pack(">H", 500)
    corpus.append(lying)
    return corpus


def _throw_garbage(port, corpus):
    """One connection per blob; sender ignores resets (that IS the graceful
    drop under test)."""
    for blob in corpus:
        try:
            s = socket.create_connection(("127.0.0.1", port), 2)
            s.sendall(blob)
            s.settimeout(0.02)
            try:
                s.recv(1024)
            except (socket.timeout, OSError):
                pass
            s.close()
        except OSError:
            pass


def _assert_still_serving(port):
    c = TokenClient("127.0.0.1", port, timeout_ms=3000)
    try:
        assert c.ping()
        out = c.request_batch_arrays(np.full(4, 1, np.int64))
        assert out is not None and (out[0] == 0).all()
        assert c.request_token(1).ok
    finally:
        c.close()
    # rev-5 control plane answers too: a lease grant after the garbage.
    # A server's first grant compiles its host-side window reads (some
    # forty one-op programs: 3.3 s with six workers beside it, against a
    # 3 s timeout, read as "granted 0"); whether it answers is the test
    lc = TokenClient("127.0.0.1", port, timeout_ms=15000, lease=True,
                     lease_want=8)
    try:
        assert lc.request_token(1).ok
        assert lc.lease_stats()["granted"] >= 1
    finally:
        lc.close()


def _lease_cut_corpus():
    """Every truncation cut of each rev-5 lease request, re-framed with an
    honest length header so the door's splitter delivers the torn payload
    intact to ``decode_lease_request`` — the containment path under test —
    plus a lease RESPONSE thrown at the server (wrong direction)."""
    corpus = []
    for mt in (P.MsgType.LEASE_GRANT, P.MsgType.LEASE_RENEW,
               P.MsgType.LEASE_RETURN):
        payload = P.encode_lease_request(
            7, mt, flow_id=1, want=9, lease_id=3, used=2
        )[2:]
        for cut in range(len(payload)):
            corpus.append(struct.pack(">H", cut) + payload[:cut])
    corpus.append(P.encode_lease_response(
        9, P.MsgType.LEASE_GRANT, 0, 5, 100, 500
    ))
    return corpus


class TestAsyncioFuzz:
    def test_garbage_never_kills_the_loop(self, asyncio_server):
        _throw_garbage(asyncio_server.port, _garbage_corpus())
        _assert_still_serving(asyncio_server.port)

    def test_torn_lease_frames_never_kill_the_loop(self, asyncio_server):
        _throw_garbage(asyncio_server.port, _lease_cut_corpus())
        _assert_still_serving(asyncio_server.port)

    def test_garbage_interleaved_with_live_traffic(self, asyncio_server):
        stop = threading.Event()

        def attacker():
            while not stop.is_set():
                _throw_garbage(asyncio_server.port, _garbage_corpus(n=5))

        t = threading.Thread(target=attacker)
        t.start()
        try:
            for _ in range(3):
                _assert_still_serving(asyncio_server.port)
        finally:
            stop.set()
            t.join(timeout=10)


@pytest.mark.skipif(not native_available(), reason="native library not built")
class TestNativeFuzz:
    def test_garbage_never_kills_a_lane(self, svc):
        server = NativeTokenServer(svc, port=0, idle_ttl_s=None)
        server.start()
        try:
            _throw_garbage(server.port, _garbage_corpus(seed=SEED + 1))
            _assert_still_serving(server.port)
        finally:
            server.stop()

    def test_torn_lease_frames_never_kill_a_lane(self, svc):
        server = NativeTokenServer(svc, port=0, idle_ttl_s=None)
        server.start()
        try:
            _throw_garbage(server.port, _lease_cut_corpus())
            _assert_still_serving(server.port)
        finally:
            server.stop()


class TestClientReaderFuzz:
    def _fake_server(self, reply_blobs):
        """Accepts one connection, streams the scripted blobs back at it."""
        lsock = socket.socket()
        lsock.bind(("127.0.0.1", 0))
        lsock.listen(4)
        port = lsock.getsockname()[1]

        def serve():
            try:
                lsock.settimeout(5)
                conn, _ = lsock.accept()
                conn.settimeout(5)
                try:
                    conn.recv(65536)  # whatever the client sent
                except OSError:
                    pass
                for blob in reply_blobs:
                    try:
                        conn.sendall(blob)
                    except OSError:
                        break
                conn.close()
            except OSError:
                pass
            finally:
                lsock.close()

        t = threading.Thread(target=serve)
        t.start()
        return port, t

    def test_reader_survives_malformed_reply(self):
        # a runt frame raises in FrameReader.feed — the reader must drop
        # the connection, never die with an unhandled exception
        port, t = self._fake_server([b"\x00\x02xx"])
        c = TokenClient("127.0.0.1", port, timeout_ms=300)
        try:
            r = c.request_token(1)
            assert not r.ok  # degraded, not raised
            # the client object stays usable (reconnect path)
            r2 = c.request_token(1)
            assert r2 is not None
        finally:
            c.close()
            t.join(timeout=5)

    def test_reader_survives_truncated_lease_response(self):
        # a runt LEASE_GRANT answer (framed honestly, payload torn) must
        # degrade like any corrupt frame: connection dropped, no dead
        # reader, the client object stays usable
        rsp = P.encode_lease_response(1, P.MsgType.LEASE_GRANT, 0, 5, 64,
                                      500)[2:]
        torn = struct.pack(">H", 7) + rsp[:7]
        port, t = self._fake_server([torn])
        c = TokenClient("127.0.0.1", port, timeout_ms=300, lease=True)
        try:
            r = c.request_token(1)
            assert r is not None and not r.ok  # degraded, not raised
        finally:
            c.close()
            t.join(timeout=5)

    def test_reader_survives_random_garbage(self):
        rng = random.Random(SEED)
        blobs = [
            bytes(rng.randrange(256) for _ in range(64)) for _ in range(8)
        ]
        port, t = self._fake_server(blobs)
        c = TokenClient("127.0.0.1", port, timeout_ms=300)
        try:
            r = c.request_token(1)
            assert r is not None and not r.ok
        finally:
            c.close()
            t.join(timeout=5)


class TestDecodeIntoFuzz:
    """The zero-copy decode entry point must agree with the reference
    decoder on every truncation cut of a valid frame: reject everywhere the
    reference rejects, match bit-for-bit everywhere it succeeds."""

    def test_every_truncation_cut_agrees_with_reference(self):
        rng = np.random.default_rng(SEED)
        ids = rng.integers(-(2**62), 2**62, size=17).astype(np.int64)
        cnt = rng.integers(-(2**31), 2**31 - 1, size=17).astype(np.int32)
        pr = rng.integers(0, 2, size=17).astype(bool)
        payload = P.encode_batch_request(42, ids, cnt, pr, deadline_ms=99)[2:]
        ids_out = np.empty(64, np.int64)
        counts_out = np.empty(64, np.int32)
        prios_out = np.empty(64, bool)
        for cut in range(len(payload) + 1):
            piece = payload[:cut]
            try:
                ref = P.decode_batch_request(piece)
            except (ValueError, struct.error):
                ref = None
            try:
                got = P.decode_batch_request_into(
                    piece, ids_out, counts_out, prios_out
                )
            except (ValueError, struct.error):
                got = None
            if ref is None:
                assert got is None, f"decode_into accepted cut={cut}"
            else:
                assert got is not None, f"decode_into rejected cut={cut}"
                xid, n = got
                assert xid == ref[0] and n == len(ref[1])
                np.testing.assert_array_equal(ids_out[:n], ref[1])
                np.testing.assert_array_equal(counts_out[:n], ref[2])
                np.testing.assert_array_equal(prios_out[:n], ref[3])

    def test_random_blobs_never_escape_valueerror(self):
        rng = random.Random(SEED + 7)
        ids_out = np.empty(64, np.int64)
        counts_out = np.empty(64, np.int32)
        prios_out = np.empty(64, bool)
        for _ in range(200):
            blob = bytes(
                rng.randrange(256) for _ in range(rng.randrange(0, 120))
            )
            try:
                P.decode_batch_request_into(
                    blob, ids_out, counts_out, prios_out
                )
            except (ValueError, struct.error):
                pass  # the only sanctioned failure modes


class TestLeaseCodecFuzz:
    """Rev-5 lease codec containment: every truncation cut raises
    ``ValueError`` (never struct.error, never an index crash), and the
    full frame round-trips bit-exact."""

    def test_request_every_cut_raises_valueerror(self):
        for mt in (P.MsgType.LEASE_GRANT, P.MsgType.LEASE_RENEW,
                   P.MsgType.LEASE_RETURN):
            payload = P.encode_lease_request(
                42, mt, flow_id=77, want=9, lease_id=1234, used=5
            )[2:]
            for cut in range(len(payload)):
                with pytest.raises(ValueError):
                    P.decode_lease_request(payload[:cut])
            got = P.decode_lease_request(payload)
            assert got == (42, mt, 1234, 77, 5, 9)

    def test_response_cuts_below_base_raise_valueerror(self):
        payload = P.encode_lease_response(
            9, P.MsgType.LEASE_RENEW, 0, lease_id=5, tokens=100, ttl_ms=500
        )[2:]
        for cut in range(len(payload)):
            with pytest.raises(ValueError):
                P.decode_lease_response(payload[:cut])
        rsp = P.decode_lease_response(payload)
        assert (rsp.xid, rsp.msg_type, rsp.status) == (
            9, P.MsgType.LEASE_RENEW, 0
        )
        assert (rsp.lease_id, rsp.tokens, rsp.ttl_ms) == (5, 100, 500)

    def test_moved_trailer_cuts_never_escape(self):
        # the MOVED endpoint trailer is variable-length: any cut at or past
        # the base struct must DECODE (shorter endpoint — possibly torn
        # mid-UTF-8, absorbed by errors="replace"), never raise
        payload = P.encode_lease_response(
            3, P.MsgType.LEASE_RENEW, P.MOVED_STATUS, tokens=7,
            endpoint="héßt:9000",
        )[2:]
        base = len(P.encode_lease_response(
            3, P.MsgType.LEASE_RENEW, P.MOVED_STATUS, tokens=7
        )[2:])
        for cut in range(len(payload) + 1):
            piece = payload[:cut]
            if cut < base:
                with pytest.raises(ValueError):
                    P.decode_lease_response(piece)
            else:
                rsp = P.decode_lease_response(piece)
                assert rsp.status == P.MOVED_STATUS
        assert P.decode_lease_response(payload).endpoint == "héßt:9000"

    def test_random_blobs_never_escape_valueerror(self):
        rng = random.Random(SEED + 5)
        for _ in range(300):
            blob = bytes(
                rng.randrange(256) for _ in range(rng.randrange(0, 64))
            )
            for decode in (P.decode_lease_request, P.decode_lease_response):
                try:
                    decode(blob)
                except ValueError:
                    pass  # the only sanctioned failure mode

    def test_decode_request_refuses_lease_types(self):
        # lease frames route through their own codec; the decision-plane
        # decoder must refuse them loudly rather than misparse the body
        frame = P.encode_lease_request(1, P.MsgType.LEASE_GRANT, 1, 4)[2:]
        with pytest.raises(ValueError):
            P.decode_request(frame)


def _push_frames():
    """One well-formed frame of each rev-7 push type (length-prefixed)."""
    return {
        "lease_revoke": P.encode_push_lease_revoke(1, 111, 55, 1, 8),
        "breaker_flip": P.encode_push_breaker_flip(2, 111, 1, 1, 60_000),
        "rule_epoch": P.encode_push_rule_epoch(3, 111, 9),
        "shard_map": P.encode_push_shard_map(4, 111, b"\x00" * 24),
        "brownout": P.encode_push_brownout(5, 111, 2, 250),
    }


def _push_cut_corpus(min_cut=0):
    """Every truncation cut of all five push frames, re-framed with an
    honest length header (the splitter delivers the torn payload intact to
    the push dispatch — the containment path under test), plus each full
    frame."""
    corpus = []
    for frame in _push_frames().values():
        payload = frame[2:]
        for cut in range(min_cut, len(payload)):
            corpus.append(struct.pack(">H", cut) + payload[:cut])
        corpus.append(frame)
    return corpus


class TestPushCodecFuzz:
    """Rev-7 push codec containment: decode either succeeds or raises
    ``ValueError`` — never struct.error, never an index crash — on every
    truncation cut, and full frames round-trip exact fields."""

    def test_every_cut_raises_valueerror_or_decodes(self):
        for name, frame in _push_frames().items():
            payload = frame[2:]
            for cut in range(len(payload)):
                try:
                    got = P.decode_push(payload[:cut])
                except ValueError:
                    continue  # the only sanctioned failure mode
                # SHARD_MAP_PUSH legitimately decodes past its stamp: the
                # doc is opaque variable-length bytes (a torn doc is the
                # shard-map DECODER's problem, contained separately)
                assert name == "shard_map", (
                    f"{name} cut={cut} decoded instead of raising"
                )
                assert got.msg_type == P.MsgType.SHARD_MAP_PUSH

    def test_full_frames_roundtrip(self):
        f = _push_frames()
        p = P.decode_push(f["lease_revoke"][2:])
        assert (p.msg_type, p.stamp_ms, p.lease_id, p.flow_id, p.tokens) == (
            P.MsgType.LEASE_REVOKE, 111, 55, 1, 8
        )
        p = P.decode_push(f["breaker_flip"][2:])
        assert (p.msg_type, p.flow_id, p.state, p.retry_after_ms) == (
            P.MsgType.BREAKER_FLIP, 1, 1, 60_000
        )
        p = P.decode_push(f["rule_epoch"][2:])
        assert (p.msg_type, p.epoch) == (P.MsgType.RULE_EPOCH_INVALIDATE, 9)
        p = P.decode_push(f["shard_map"][2:])
        assert (p.msg_type, p.doc) == (P.MsgType.SHARD_MAP_PUSH, b"\x00" * 24)
        p = P.decode_push(f["brownout"][2:])
        assert (p.msg_type, p.level, p.retry_after_ms) == (
            P.MsgType.BROWNOUT_ADVISORY, 2, 250
        )

    def test_random_blobs_never_escape_valueerror(self):
        rng = random.Random(SEED + 11)
        for _ in range(300):
            blob = bytes(
                rng.randrange(256) for _ in range(rng.randrange(0, 80))
            )
            try:
                P.decode_push(blob)
            except ValueError:
                pass  # the only sanctioned failure mode

    def test_decode_request_refuses_push_types(self):
        # pushes are server→client only; the decision-plane decoder must
        # refuse them loudly rather than misparse the body as a request
        for frame in _push_frames().values():
            with pytest.raises(ValueError):
                P.decode_request(frame[2:])

    def test_decode_push_refuses_non_push_types(self):
        frame = P.encode_request(P.Ping(1))
        with pytest.raises(ValueError):
            P.decode_push(frame[2:])


class TestAsyncioPushDirectionFuzz:
    def test_push_frames_thrown_at_the_server_never_kill_the_loop(
        self, asyncio_server
    ):
        # wrong-direction traffic: a client (or attacker) streaming push
        # frames AT the door must get a graceful drop, not a dead lane
        _throw_garbage(asyncio_server.port, _push_cut_corpus())
        _assert_still_serving(asyncio_server.port)


class TestClientPushFuzz:
    """Torn pushes into the TCP reader: every cut that still carries the
    push type byte is counted-and-skipped WITHOUT dropping the connection
    (a push gates no pending request), valid pushes interleaved with the
    garbage still apply, and lease state stays consistent."""

    def _fake_server(self, reply_blobs):
        return TestClientReaderFuzz._fake_server(self, reply_blobs)

    def test_torn_pushes_skip_and_count_valid_pushes_apply(self):
        from sentinel_tpu.engine import TokenStatus

        # cuts below the xid+type header hit the generic runt path (covered
        # by TestClientReaderFuzz); from the header on, push containment
        # owns the frame — stream those, then prove the SAME connection
        # still delivers: a full breaker flip must apply after the garbage
        blobs = _push_cut_corpus(min_cut=P._HEAD.size)
        flip = P.encode_push_breaker_flip(9, 111, 1, 1, 60_000)
        blobs.append(flip)
        port, t = self._fake_server(blobs)
        c = TokenClient("127.0.0.1", port, timeout_ms=300, lease=True)
        try:
            c.request_token(1)  # connects; times out (no verdict scripted)
            deadline = 50
            while c.push_stats().get("breaker_flip", 0) < 1:
                deadline -= 1
                assert deadline > 0, "breaker flip push never applied"
                threading.Event().wait(0.05)
            stats = c.push_stats()
            # torn frames were counted, not fatal: the flip arrived LAST on
            # the same connection, so the reader survived every cut
            assert stats["malformed"] > 0
            # the pushed OPEN answers locally while the clock runs
            r = c.request_token(1)
            assert r.status == TokenStatus.DEGRADED
            assert r.wait_ms > 0
            # lease consistency: the revoke cuts and the full revoke for an
            # unknown lease id left no phantom lease behind
            assert not c._leases
        finally:
            c.close()
            t.join(timeout=5)

    def test_unknown_frame_types_skip_and_count(self):
        from sentinel_tpu.cluster.client import client_unknown_frames_total

        base = client_unknown_frames_total()
        future = struct.pack(">H", 9) + struct.pack(">ib", 7, 99) + b"\0" * 4
        flip = P.encode_push_breaker_flip(9, 111, 2, 1, 60_000)
        port, t = self._fake_server([future, flip])
        c = TokenClient("127.0.0.1", port, timeout_ms=300)
        try:
            c.request_token(2)
            deadline = 50
            while c.push_stats().get("breaker_flip", 0) < 1:
                deadline -= 1
                assert deadline > 0, "flip after unknown frame never applied"
                threading.Event().wait(0.05)
            # the unknown frame was skipped+counted, and the connection
            # survived to deliver the flip behind it
            assert client_unknown_frames_total() > base
        finally:
            c.close()
            t.join(timeout=5)


@pytest.mark.skipif(not native_available(), reason="native library not built")
class TestShmPushFuzz:
    """Torn pushes down the shm ring's response lane: the ring client's
    reader shares the TCP reader's containment (skip + count, never a dead
    lane), and the lane keeps serving verdicts afterwards."""

    def test_torn_pushes_never_kill_the_ring_lane(self, svc, tmp_path):
        from sentinel_tpu.cluster.shm_client import ShmTokenClient
        from sentinel_tpu.engine import TokenStatus

        shm_dir = str(tmp_path)
        server = NativeTokenServer(
            svc, port=0, idle_ttl_s=None, shm_dir=shm_dir
        )
        server.start()
        c = None
        try:
            c = ShmTokenClient(shm_dir, timeout_ms=3000)
            assert c.request_token(1).ok  # lane up, sink attached
            deadline = 100
            while not server.push_hub.connections():
                deadline -= 1
                assert deadline > 0, "shm connection never attached a sink"
                threading.Event().wait(0.05)
            # inject every truncation cut straight into the response lane
            with server.push_hub._lock:
                sinks = list(server.push_hub._sinks.values())
            for blob in _push_cut_corpus(min_cut=P._HEAD.size):
                for sink in sinks:
                    sink(blob)  # sinks take the length-prefixed frame
            # a real flip behind the garbage still applies...
            server.push_hub.push_breaker_flip(1, 1, 60_000)
            deadline = 100
            while c.push_stats().get("breaker_flip", 0) < 1:
                deadline -= 1
                assert deadline > 0, "breaker flip push never applied"
                threading.Event().wait(0.05)
            assert c.push_stats()["malformed"] > 0
            assert c.request_token(1).status == TokenStatus.DEGRADED
            # ...and the lane still serves once the clock is lifted
            server.push_hub.push_breaker_flip(1, 0, 0)
            deadline = 100
            while c.request_token(1).status == TokenStatus.DEGRADED:
                deadline -= 1
                assert deadline > 0, "pushed CLOSED never lifted the clock"
                threading.Event().wait(0.05)
            assert c.request_token(1).ok
            assert not c._leases
        finally:
            if c is not None:
                c.close()
            server.stop()


@pytest.mark.skipif(not native_available(), reason="native library not built")
class TestShardedNativeFuzz:
    def test_garbage_never_kills_a_sharded_lane(self, svc):
        server = NativeTokenServer(
            svc, port=0, idle_ttl_s=None, intake_shards=2
        )
        server.start()
        try:
            # double the corpus: with two doors behind one port the kernel
            # spreads connections, so both intake lanes eat garbage
            _throw_garbage(server.port, _garbage_corpus(seed=SEED + 2))
            _throw_garbage(server.port, _garbage_corpus(seed=SEED + 3))
            _assert_still_serving(server.port)
        finally:
            server.stop()
