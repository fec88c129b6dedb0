"""Wire-rev-7 push plane: hub semantics, service emit sites, and E2E
server→client delivery.

The hub must be fire-and-forget (a raising sink drops the frame, nothing
retries, nothing blocks) and disarmable (``enabled=False`` — the drills'
push-dark mode). The service must emit LEASE_REVOKE on every lease-killing
path (TTL sweep, rule reload, MOVE recall), RULE_EPOCH_INVALIDATE on rule
reload, and BREAKER_FLIP on device breaker edges. End-to-end over the
asyncio door: a rule reload lands on a leased client as revoke +
invalidate within the poll budget, a brownout transition reaches
``on_brownout``, and a shard-map push re-routes a RoutingTokenClient.
"""

import threading
import time

import numpy as np
import pytest

from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.push import PushHub
from sentinel_tpu.cluster.rebalance import (
    ShardMap,
    decode_shard_map_doc,
    encode_shard_map_doc,
)
from sentinel_tpu.cluster.routing import RoutingTokenClient
from sentinel_tpu.cluster.server import TokenServer
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
from sentinel_tpu.engine.rules import ThresholdMode

G = ThresholdMode.GLOBAL
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=64)
FLOW = 11


def _service():
    svc = DefaultTokenService(CFG)
    svc.load_rules([ClusterFlowRule(FLOW, 1e9, G)])
    return svc


def _wait(predicate, what, timeout_s=3.0):
    deadline = time.monotonic() + timeout_s
    while not predicate():
        assert time.monotonic() < deadline, what
        time.sleep(0.02)


def _split_pushes(data: bytes):
    """The push frames of one write, each under its own length prefix."""
    out, pos = [], 0
    while pos < len(data):
        n = int.from_bytes(data[pos:pos + 2], "big")
        out.append(P.decode_push(data[pos + 2:pos + 2 + n]))
        pos += 2 + n
    return out


class _Recorder:
    """A sink/hub stub that records everything and can be told to raise."""

    def __init__(self, raising=False):
        self.frames = []
        self.calls = []
        self.raising = raising

    def sink(self, frame: bytes):
        if self.raising:
            raise OSError("sink closed")
        self.frames.append(frame)

    def __getattr__(self, name):
        if not name.startswith("push_"):
            raise AttributeError(name)

        def emit(*args):
            self.calls.append((name, args))

        return emit


class TestPushHub:
    def test_broadcast_reaches_every_sink(self):
        hub = PushHub()
        a, b = _Recorder(), _Recorder()
        hub.attach("a", a.sink)
        hub.attach("b", b.sink)
        assert hub.push_breaker_flip(FLOW, 1, 500) == 2
        assert len(a.frames) == len(b.frames) == 1
        push = P.decode_push(a.frames[0][2:])
        assert (push.msg_type, push.flow_id, push.state) == (
            P.MsgType.BREAKER_FLIP, FLOW, 1
        )
        assert push.stamp_ms > 0

    def test_a_scan_s_flips_ride_one_write_per_sink(self):
        hub = PushHub()
        a, b = _Recorder(), _Recorder()
        hub.attach("a", a.sink)
        hub.attach("b", b.sink)
        flips = [(FLOW, 1, 500), (FLOW + 1, 2, 0), (FLOW + 2, 0, 0)]
        assert hub.push_breaker_flips(flips) == 2
        assert len(a.frames) == len(b.frames) == 1  # one write each
        got = [(p.flow_id, p.state, p.retry_after_ms, p.stamp_ms)
               for p in _split_pushes(a.frames[0])]
        assert [g[:3] for g in got] == flips
        assert len({g[3] for g in got}) == 1  # one stamp for the scan
        assert hub.stats()["sent"]["breaker_flip"] == 6  # frames, not writes
        assert hub.push_breaker_flips([]) == 0
        assert len(a.frames) == 1

    def test_raising_sink_drops_silently_and_counts(self):
        hub = PushHub()
        good, bad = _Recorder(), _Recorder(raising=True)
        hub.attach("good", good.sink)
        hub.attach("bad", bad.sink)
        assert hub.push_lease_revoke(5, FLOW, 8) == 1  # the good sink
        assert len(good.frames) == 1
        stats = hub.stats()
        assert stats["dropped"] == 1
        assert stats["sent"]["lease_revoke"] == 1

    def test_disabled_hub_is_a_no_op(self):
        hub = PushHub(enabled=False)
        rec = _Recorder()
        hub.attach("a", rec.sink)
        assert hub.push_breaker_flip(FLOW, 1, 0) == 0
        assert hub.push_rule_epoch(3) == 0
        assert hub.push_brownout(2, 100) == 0
        assert not rec.frames
        assert hub.stats()["enabled"] is False

    def test_detach_and_reattach_replace_the_sink(self):
        hub = PushHub()
        first, second = _Recorder(), _Recorder()
        hub.attach("conn", first.sink)
        hub.attach("conn", second.sink)  # reconnect under the same key
        hub.push_brownout(1, 50)
        assert not first.frames and len(second.frames) == 1
        hub.detach("conn")
        assert hub.connections() == 0
        assert hub.push_brownout(1, 50) == 0

    def test_oversized_shard_map_is_dropped_not_raised(self):
        hub = PushHub()
        rec = _Recorder()
        hub.attach("a", rec.sink)
        assert hub.push_shard_map(b"\x00" * (P.MAX_FRAME + 1)) == 0
        assert not rec.frames
        assert hub.stats()["dropped"] == 1


class TestServiceEmitSites:
    def test_rule_reload_emits_epoch_invalidate_and_revokes(self):
        svc = _service()
        hub = _Recorder()
        svc.attach_push_hub(hub)
        grant = svc.lease_grant(FLOW, 16)
        assert grant.tokens > 0
        # the reload drops FLOW's rule → its lease is dead and must be
        # recalled by push, not left to ride out its TTL
        svc.load_rules([ClusterFlowRule(FLOW + 1, 1e9, G)])
        revokes = [c for c in hub.calls if c[0] == "push_lease_revoke"]
        epochs = [c for c in hub.calls if c[0] == "push_rule_epoch"]
        assert len(revokes) == 1
        assert revokes[0][1][:2] == (grant.lease_id, FLOW)
        assert len(epochs) == 1 and epochs[0][1][0] > 0

    def test_expired_lease_sweep_emits_revoke(self):
        svc = _service()
        hub = _Recorder()
        svc.attach_push_hub(hub)
        grant = svc.lease_grant(FLOW, 16)
        assert grant.tokens > 0
        # renew with a dead remote clock: force expiry by sweeping far in
        # the future through the renewal path's sweep hook
        with svc._lock:
            for lease in svc._leases.values():
                lease.expiry_ms = 0
            svc._sweep_leases_locked(now=1)
        revokes = [c for c in hub.calls if c[0] == "push_lease_revoke"]
        assert len(revokes) == 1
        assert revokes[0][1][0] == grant.lease_id

    def test_breaker_scan_emits_its_edges_in_one_call(self, manual_clock):
        from sentinel_tpu.engine.rules import DegradeRule, DegradeStrategy

        manual_clock.set_ms(1_700_000_000_000)
        svc = DefaultTokenService(CFG)
        svc.load_rules([ClusterFlowRule(f, 1e9, G) for f in (1, 2, 3)])
        svc.load_degrade_rules([
            DegradeRule(f, DegradeStrategy.ERROR_COUNT, 5,
                        recovery_timeout_ms=700 + f) for f in (1, 2)])
        hub = _Recorder()
        svc.attach_push_hub(hub)
        bad = np.repeat([1, 2, 3], 8)  # flow 3 has no breaker
        svc.report_outcomes(bad, np.full(24, 5), np.ones(24, bool))
        manual_clock.advance(10)
        svc.request_batch_arrays(np.array([1, 2, 3], np.int64),
                                 np.ones(3, np.int32), np.zeros(3, bool))
        svc._breaker_scan(force=True)
        calls = [c for c in hub.calls if c[0].startswith("push_breaker")]
        assert len(calls) == 1 and calls[0][0] == "push_breaker_flips"
        # OPEN carries the rule's recovery timeout as the retry-after
        assert sorted(calls[0][1][0]) == [(1, 1, 701), (2, 1, 702)]
        svc._breaker_scan(force=True)  # nothing changed: nothing said
        assert len([c for c in hub.calls
                    if c[0].startswith("push_breaker")]) == 1

    def test_shm_door_gives_each_frame_of_a_batch_its_own_slot(self):
        from sentinel_tpu.native.lib import ShmDoor

        sent = []

        class Lib:
            @staticmethod
            def sn_shm_send(h, fd, gen, payload, n):
                sent.append((fd, gen, bytes(payload[:n])))

        door = object.__new__(ShmDoor)
        door._lib, door._h = Lib, None
        one = P.encode_push_breaker_flip(1, 111, 5, 1, 600)
        two = P.encode_push_breaker_flip(2, 111, 6, 0, 0)
        door.send(7, 3, one)
        door.send(7, 3, one + two)
        assert sent == [(7, 3, one[2:]), (7, 3, one[2:]), (7, 3, two[2:])]

    def test_emit_survives_a_raising_hub(self):
        svc = _service()

        class Hostile:
            def __getattr__(self, name):
                raise RuntimeError("hub torn down")

        svc.attach_push_hub(Hostile())
        svc.load_rules([ClusterFlowRule(FLOW, 1e9, G)])  # must not raise


@pytest.fixture(scope="module")
def push_server():
    svc = _service()
    server = TokenServer(svc, port=0)
    server.start()
    yield server
    server.stop()


class TestPushE2E:
    def test_rule_reload_revokes_leased_client_within_poll_budget(
        self, push_server
    ):
        c = TokenClient("127.0.0.1", push_server.port, timeout_ms=2000,
                        lease=True, lease_want=64)
        try:
            assert c.request_token(FLOW).ok
            _wait(lambda: c.lease_stats()["granted"] >= 1,
                  "lease never granted")
            push_server.service.load_rules(
                [ClusterFlowRule(FLOW, 1e9, G)]
            )
            _wait(lambda: c.push_stats()["rule_epoch_invalidate"] >= 1,
                  "epoch invalidate never arrived")
            _wait(lambda: not c._leases, "pushed revoke never dropped lease")
            # the connection survived and the flow still serves
            assert c.request_token(FLOW).ok
        finally:
            c.close()

    def test_brownout_transition_reaches_on_brownout(self, push_server):
        c = TokenClient("127.0.0.1", push_server.port, timeout_ms=2000)
        got = []
        c.on_brownout = lambda level, retry: got.append((level, retry))
        try:
            assert c.ping()  # connection up, sink attached
            # drive the admission controller's transition listener exactly
            # as _evaluate does — the server wired it to push_brownout
            push_server.overload.on_level_change(2, 250)
            _wait(lambda: got, "brownout advisory never arrived")
            assert got[0] == (2, 250)
        finally:
            c.close()

    def test_shard_map_push_rewires_routing_client(self, push_server):
        ns = "default"
        router = RoutingTokenClient(
            timeout_ms=2000,
            namespace_of={FLOW: ns},
            pod_of={ns: "pod-a"},
            endpoints={"pod-a": ("127.0.0.1", push_server.port)},
        )
        try:
            assert router.request_token(FLOW).ok  # builds the pod client
            pushed = ShardMap(
                epoch=7,
                endpoint_of={ns: f"127.0.0.1:{push_server.port}"},
                global_flows={str(FLOW): "10.9.9.9:7000"},
            )
            push_server.push_hub.push_shard_map(encode_shard_map_doc(pushed))
            _wait(lambda: router.epoch == 7,
                  "pushed shard map never applied")
            assert router.coordinator_of(FLOW) == "10.9.9.9:7000"
            # stale epoch pushed later is fenced out
            stale = ShardMap(epoch=3, endpoint_of={},
                             global_flows={str(FLOW): "10.0.0.1:1"})
            push_server.push_hub.push_shard_map(encode_shard_map_doc(stale))
            time.sleep(0.1)
            assert router.coordinator_of(FLOW) == "10.9.9.9:7000"
        finally:
            router.close()

    def test_push_dark_server_sends_nothing(self):
        svc = _service()
        server = TokenServer(svc, port=0, push=False)
        server.start()
        c = TokenClient("127.0.0.1", server.port, timeout_ms=2000,
                        lease=True, lease_want=64)
        try:
            assert c.request_token(FLOW).ok
            _wait(lambda: c.lease_stats()["granted"] >= 1,
                  "lease never granted")
            svc.load_rules([ClusterFlowRule(FLOW, 1e9, G)])
            time.sleep(0.3)
            # no push arrived; the client learns at its own pace (TTL /
            # next wire refusal) — exactly the rev-6 staleness bound
            assert c.push_stats()["rule_epoch_invalidate"] == 0
            assert server.push_hub.stats()["sent"] == {}
        finally:
            c.close()
            server.stop()


class TestShardMapDocCodec:
    def test_roundtrip(self):
        m = ShardMap(epoch=9, endpoint_of={"ns": "h:1"},
                     global_flows={"7": "h:2"})
        got = decode_shard_map_doc(encode_shard_map_doc(m))
        assert (got.epoch, dict(got.endpoint_of), dict(got.global_flows)) \
            == (9, {"ns": "h:1"}, {"7": "h:2"})

    def test_garbage_raises_valueerror_only(self):
        for blob in (b"", b"\x00", b"not zlib at all", b"x" * 64):
            with pytest.raises(ValueError):
                decode_shard_map_doc(blob)
