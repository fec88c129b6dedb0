"""Warm-standby replication: codecs, delta apply, promotion under chaos.

Tentpole suite for the lossless-failover PR. Layers under test, bottom up:

- rev-3 frame codecs (hello/ack/chunked blobs) round-trip and reject torn
  or fuzzed input at the parse boundary;
- ``export_delta``/``apply_replication_delta`` converge a standby's
  counters bit-for-bit with the primary's, including ring rotation and the
  generation fence;
- the full sender→applier stack over real servers: a standby refuses with
  STANDBY while replicating, survives ``conn_reset``/``lane_delay`` chaos
  on the repl channel, and after promotion serves with counters inside the
  staleness budget (one delta-ship interval).

Satellite regressions ride along: torn snapshot artifacts, datasource
refresh backoff + last-known-good, heartbeat backoff jitter.
"""

import json
import os
import random
import time

import numpy as np
import pytest

from sentinel_tpu import chaos
from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.server import TokenServer
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
from sentinel_tpu.engine.decide import TokenStatus
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.ha import FailoverTokenClient
from sentinel_tpu.ha import replication as R
from sentinel_tpu.metrics.ha import ha_metrics, reset_ha_metrics_for_tests

G = ThresholdMode.GLOBAL
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=64)
SEED = 0xB10B

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    chaos.disarm()


def _service(count=1e9):
    svc = DefaultTokenService(CFG)
    svc.load_rules([ClusterFlowRule(flow_id=1, count=count, mode=G)])
    return svc


def _payload(frame: bytes) -> bytes:
    """Strip the 2-byte length prefix (the codecs emit wire frames; the
    decoders take what a reader hands them after de-framing)."""
    assert int.from_bytes(frame[:2], "big") == len(frame) - 2
    return frame[2:]


# -- rev-3 frame codecs ------------------------------------------------------
class TestReplCodec:
    def test_hello_roundtrip(self):
        pay = _payload(
            P.encode_repl_hello(7, 3, 1234, 56, sender_id="10.0.0.1:9000")
        )
        xid, gen, epoch, seq, sender = P.decode_repl_hello(pay)
        assert (xid, gen, epoch, seq) == (7, 3, 1234, 56)
        assert sender == "10.0.0.1:9000"
        assert P.peek_type(pay) == P.MsgType.REPL_HELLO

    def test_ack_roundtrip(self):
        pay = _payload(P.encode_repl_ack(9, P.ReplAck.NEED_SNAPSHOT, 4, 100))
        xid, code, gen, seq = P.decode_repl_ack(pay)
        assert (xid, code, gen, seq) == (9, P.ReplAck.NEED_SNAPSHOT, 4, 100)
        assert isinstance(code, P.ReplAck)

    @pytest.mark.parametrize("size", [0, 1, 1000, 200_000])
    def test_blob_chunk_roundtrip(self, size):
        blob = bytes(random.Random(SEED + size).randrange(256)
                     for _ in range(size))
        frames = P.encode_repl_blob(5, P.MsgType.REPL_DELTA, 2, 11, blob)
        # every frame's payload fits the 16-bit length prefix
        assert all(len(f) - 2 <= P.MAX_FRAME for f in frames)
        asm = P.ReplBlobAssembler()
        out = None
        for f in frames:
            assert out is None  # incomplete until the last chunk
            pay = _payload(f)
            out = asm.feed(P.peek_type(pay), pay)
        mtype, gen, seq, got = out
        assert (mtype, gen, seq) == (P.MsgType.REPL_DELTA, 2, 11)
        assert got == blob

    def test_blob_fuzz_roundtrip(self):
        rng = random.Random(SEED)
        asm = P.ReplBlobAssembler()
        for trial in range(25):
            blob = os.urandom(rng.randrange(0, 150_000))
            frames = P.encode_repl_blob(
                trial, P.MsgType.REPL_SNAPSHOT, 1, trial, blob
            )
            out = None
            for f in frames:
                pay = _payload(f)
                out = asm.feed(P.peek_type(pay), pay)
            assert out is not None and out[3] == blob

    def test_assembler_rejects_torn_stream(self):
        blob = bytes(200_000)
        frames = P.encode_repl_blob(1, P.MsgType.REPL_DELTA, 1, 1, blob)
        assert len(frames) >= 3
        asm = P.ReplBlobAssembler()
        p0, p2 = _payload(frames[0]), _payload(frames[2])
        asm.feed(P.peek_type(p0), p0)
        with pytest.raises(ValueError):
            asm.feed(P.peek_type(p2), p2)  # gap: skipped idx 1
        # the torn stream cleared assembler state; a fresh blob still lands
        out = None
        for f in P.encode_repl_blob(2, P.MsgType.REPL_DELTA, 1, 2, b"ok"):
            pay = _payload(f)
            out = asm.feed(P.peek_type(pay), pay)
        assert out is not None and out[3] == b"ok"

    def test_chunk_decode_rejects_runt(self):
        with pytest.raises(ValueError):
            P.decode_repl_chunk(b"\x00\x00\x00\x01\x07")

    def test_delta_blob_rejects_garbage(self):
        rng = random.Random(SEED)
        for _ in range(20):
            with pytest.raises(ValueError):
                R.decode_delta_blob(os.urandom(rng.randrange(1, 4096)))
        with pytest.raises(ValueError):
            R.decode_delta_blob(b"")


# -- delta export/apply ------------------------------------------------------
class TestDeltaApply:
    def test_counters_converge_bit_for_bit(self, manual_clock):
        # frozen clock: on a loaded host the wall between the two
        # metrics_snapshot reads below can cross a 100ms bucket boundary,
        # expiring one admission from the second read but not the first
        primary = _service()
        standby = _service()
        primary.replication_enable()
        # bootstrap: standby restores the primary's full state once
        standby.import_state(
            R.decode_snapshot_blob(
                R.encode_snapshot_blob(primary.export_state())
            )
        )
        for _ in range(17):
            primary.request_token(1)
        delta = R.decode_delta_blob(
            R.encode_delta_blob(primary.export_delta())
        )
        standby.apply_replication_delta(delta)
        p = primary.metrics_snapshot()
        s = standby.metrics_snapshot()
        assert p[1]["pass_qps"] == s[1]["pass_qps"] > 0

    def _param_service(self, slim_width=256):
        from sentinel_tpu.cluster.token_service import ClusterParamFlowRule
        from sentinel_tpu.engine.param import ParamConfig

        svc = DefaultTokenService(
            CFG,
            param_config=ParamConfig(
                max_param_rules=8, impl="jax", slim_width=slim_width
            ),
        )
        svc.load_param_rules([ClusterParamFlowRule(flow_id=9, count=5.0)])
        return svc

    def test_param_slim_delta_carries_enforcement(self, manual_clock):
        """Deltas ship the SF slim twin, not fat rows — and the slim rows
        alone must carry enforcement: a value the primary exhausted AFTER
        the bootstrap snapshot must be blocked on the promoted standby."""
        from sentinel_tpu.engine.param import ParamConfig  # noqa: F401

        primary = self._param_service()
        standby = self._param_service()
        primary.replication_enable()
        standby.import_state(
            R.decode_snapshot_blob(
                R.encode_snapshot_blob(primary.export_state())
            )
        )
        hot = 0x7E57_C0DE
        blocked = False
        for _ in range(30):
            if primary.request_params_token(9, 1, [hot]).status \
                    == TokenStatus.BLOCKED:
                blocked = True
        assert blocked, "primary never exhausted the param threshold"
        delta = R.decode_delta_blob(R.encode_delta_blob(primary.export_delta()))
        assert "param_slim" in delta and "param_counts" not in delta
        standby.apply_replication_delta(delta)
        # fat counters on the standby are still snapshot-stale (all zero);
        # the slim rows shipped in the delta must block on their own
        r = standby.request_params_token(9, 1, [hot])
        assert r.status == TokenStatus.BLOCKED

    def test_param_slim_delta_bytes_4x_under_fat(self, manual_clock):
        """Identical traffic, identical dirty slots: the slim-twin delta
        blob must come in ≥4× under the fat-row delta blob (the per-tick
        replication cost the SF split exists to cut)."""
        import numpy as np

        rng = np.random.default_rng(SEED)
        vals = rng.integers(-2 ** 63, 2 ** 63 - 1, size=1500, dtype=np.int64)
        sizes = {}
        for label, slim_width in (("slim", 256), ("fat", 0)):
            svc = self._param_service(slim_width=slim_width)
            svc.replication_enable()
            for off in range(0, len(vals), 60):
                svc.request_params_token(
                    9, 1, [int(h) for h in vals[off:off + 60]]
                )
            sizes[label] = len(R.encode_delta_blob(svc.export_delta()))
        assert sizes["fat"] >= 4 * sizes["slim"], sizes

    @pytest.mark.parametrize("standby_devices", [1, 4])
    def test_mesh_primary_delta_converges(self, standby_devices):
        """PR-7 sharded replication: a mesh-backed primary's export_delta
        (shard-aware host row gather) lands bit-for-bit on a standby with
        a DIFFERENT mesh shape — through the real rev-3 blob codecs."""
        import jax

        from sentinel_tpu.parallel import make_flow_mesh

        mesh = make_flow_mesh()
        primary = DefaultTokenService(CFG, mesh=mesh)
        primary.load_rules(
            [ClusterFlowRule(flow_id=i, count=1e9, mode=G) for i in range(16)]
        )
        primary.replication_enable()
        standby_mesh = (
            None if standby_devices == 1
            else make_flow_mesh(jax.devices()[:standby_devices])
        )
        standby = DefaultTokenService(CFG, mesh=standby_mesh)
        standby.import_state(
            R.decode_snapshot_blob(
                R.encode_snapshot_blob(primary.export_state())
            )
        )
        ids = np.tile(np.arange(16, dtype=np.int64), 8)
        primary.request_batch_arrays(ids)
        delta = R.decode_delta_blob(
            R.encode_delta_blob(primary.export_delta())
        )
        assert delta.get("flow_ids"), "dirty rows expected"
        standby.apply_replication_delta(delta)
        np.testing.assert_array_equal(
            np.asarray(standby._state.flow.counts),
            np.asarray(primary._state.flow.counts),
        )
        np.testing.assert_array_equal(
            np.asarray(standby._state.ns.counts),
            np.asarray(primary._state.ns.counts),
        )
        if standby_mesh is not None:
            assert (
                len(standby._state.flow.counts.addressable_shards)
                == standby_devices
            )
        primary.close()
        standby.close()

    def test_idle_tick_ships_heartbeat_delta(self):
        primary = _service()
        standby = _service()
        primary.replication_enable()
        standby.import_state(primary.export_state())
        delta = primary.export_delta()
        assert "flow_ids" not in delta  # nothing dirty
        standby.apply_replication_delta(
            R.decode_delta_blob(R.encode_delta_blob(delta))
        )  # starts-only delta applies cleanly

    def test_generation_fences_slot_reuse(self):
        primary = _service()
        primary.replication_enable()
        gen0 = primary.state_generation()
        primary.load_rules([ClusterFlowRule(flow_id=2, count=10, mode=G)])
        assert primary.state_generation() == gen0 + 1
        assert primary.export_delta()["gen"] == gen0 + 1

    def test_epoch_mismatch_rejected(self):
        primary = _service()
        standby = _service()
        primary.replication_enable()
        standby.import_state(primary.export_state())
        delta = primary.export_delta()
        delta["epoch_ms"] = delta["epoch_ms"] + 1
        with pytest.raises(ValueError):
            standby.apply_replication_delta(delta)

    def test_unknown_flow_rejected(self):
        primary = DefaultTokenService(CFG)
        primary.load_rules([
            ClusterFlowRule(flow_id=1, count=10, mode=G),
            ClusterFlowRule(flow_id=9, count=10, mode=G),
        ])
        primary.replication_enable()
        primary.request_token(9)
        delta = primary.export_delta()
        standby = _service()  # only knows flow 1
        # align the epoch fence so the test reaches the flow-id remap
        standby._epoch_ms = int(delta["epoch_ms"])
        with pytest.raises(ValueError):
            standby.apply_replication_delta(delta)


# -- circuit-breaker columns across the HA planes ----------------------------
class TestBreakerColumnsAcrossHA:
    """The breaker state machine must survive every serialization plane: an
    OPEN breaker that a standby or MOVE destination silently restores as
    CLOSED would re-admit a failing dependency exactly when the primary had
    fenced it off. Deltas ship the three columns under their own dirty set;
    snapshots restore them bit-exact (and tolerate their absence in
    pre-breaker artifacts); MOVE blobs carry RELATIVE clocks so the
    retry-after countdown is frozen in transit and re-anchors on import."""

    def _breaker_service(self, recovery_ms=2000):
        from sentinel_tpu.engine import DegradeRule, DegradeStrategy

        svc = DefaultTokenService(CFG)
        svc.load_rules([
            ClusterFlowRule(flow_id=1, count=1e9, mode=G, namespace="brns")
        ])
        svc.load_degrade_rules([
            DegradeRule(1, DegradeStrategy.ERROR_RATIO, threshold=0.2,
                        min_request_amount=5, stat_interval_ms=1000,
                        recovery_timeout_ms=recovery_ms, namespace="brns"),
        ])
        return svc

    def _trip(self, svc, mc):
        """Report an error burst, then decide once: CLOSED→OPEN. Returns
        the DEGRADED verdict's retry-after-ms."""
        svc.report_outcomes(
            np.full(8, 1, np.int64), np.full(8, 5, np.int64),
            np.ones(8, np.int64),
        )
        mc.advance(50)
        st, rem, _ = svc.request_batch_arrays(np.array([1], np.int64))
        assert int(np.asarray(st)[0]) == int(TokenStatus.DEGRADED)
        return int(np.asarray(rem)[0])

    def _assert_breaker_equal(self, a, b):
        for leaf_a, leaf_b in zip(a._state.breaker, b._state.breaker):
            np.testing.assert_array_equal(
                np.asarray(leaf_a), np.asarray(leaf_b)
            )

    def test_breaker_rows_ship_in_delta_and_dirty_set_drains(
        self, manual_clock
    ):
        manual_clock.advance(1_000)
        primary = self._breaker_service()
        standby = self._breaker_service()
        primary.replication_enable()
        standby.import_state(
            R.decode_snapshot_blob(
                R.encode_snapshot_blob(primary.export_state())
            )
        )
        self._trip(primary, manual_clock)
        delta = R.decode_delta_blob(
            R.encode_delta_blob(primary.export_delta())
        )
        assert delta.get("breaker_fids") == [1]
        assert int(np.asarray(delta["breaker_state"])[0]) != 0  # OPEN ships
        standby.apply_replication_delta(delta)
        self._assert_breaker_equal(standby, primary)
        # collect-and-clear: with no new breaker activity the next delta
        # carries no breaker rows (heartbeat-sized, not O(breakers))
        assert "breaker_fids" not in primary.export_delta()

    def test_snapshot_roundtrip_bit_exact_and_tolerant_absent(
        self, manual_clock
    ):
        manual_clock.advance(1_000)
        donor = self._breaker_service()
        self._trip(donor, manual_clock)
        doc = R.decode_snapshot_blob(
            R.encode_snapshot_blob(donor.export_state())
        )
        twin = DefaultTokenService(CFG)
        twin.import_state(doc)
        self._assert_breaker_equal(twin, donor)
        assert int(np.asarray(twin._state.breaker.state)[
            twin._index.slot_of[1]]) != 0
        # pre-breaker artifact: no "breaker" key → restore CLOSED/cold,
        # which under-protects briefly but never wrongly rejects
        doc2 = R.decode_snapshot_blob(
            R.encode_snapshot_blob(donor.export_state())
        )
        doc2.pop("breaker")
        cold = DefaultTokenService(CFG)
        cold.import_state(doc2)
        assert (np.asarray(cold._state.breaker.state) == 0).all()
        # the restored outcome telemetry still shows the error burst, so
        # the cold breaker legitimately RE-trips on its first decide …
        st, _, _ = cold.request_batch_arrays(np.array([1], np.int64))
        assert int(np.asarray(st)[0]) == int(TokenStatus.DEGRADED)
        # … but the donor's OPEN countdown was forgotten: once the stat
        # window drains past the re-trip fence, the flow serves again
        manual_clock.advance(2_100)
        st, _, _ = cold.request_batch_arrays(np.array([1], np.int64))
        assert int(np.asarray(st)[0]) == int(TokenStatus.OK)

    def test_move_blob_freezes_retry_countdown_in_transit(self, manual_clock):
        from sentinel_tpu.cluster.rebalance import (
            decode_move_state_blob,
            encode_move_state_blob,
        )

        manual_clock.advance(1_000)
        src = self._breaker_service(recovery_ms=2000)
        self._trip(src, manual_clock)
        manual_clock.advance(300)  # burn 300ms of the 2000ms recovery
        st, rem, _ = src.request_batch_arrays(np.array([1], np.int64))
        assert int(np.asarray(st)[0]) == int(TokenStatus.DEGRADED)
        rem_at_export = int(np.asarray(rem)[0])
        blob = encode_move_state_blob(src.export_namespace_state("brns"))
        # 450ms of transit: the blob carries clocks RELATIVE to export
        # time, so the countdown must NOT tick while the bytes are in
        # flight — the destination owes the dependency the full remaining
        # quiet period, however long the MOVE took
        manual_clock.advance(450)
        dest = DefaultTokenService(CFG)
        dest.import_namespace_state(decode_move_state_blob(blob))
        st_d, rem_d, _ = dest.request_batch_arrays(np.array([1], np.int64))
        assert int(np.asarray(st_d)[0]) == int(TokenStatus.DEGRADED)
        assert int(np.asarray(rem_d)[0]) == rem_at_export
        assert (
            dest.breaker_stats()["flows"][1]["state_code"]
            == src.breaker_stats()["flows"][1]["state_code"]
        )


# -- sender → applier over real servers, chaos on the channel ----------------
class TestPromotionUnderChaos:
    def test_standby_promotion_with_chaotic_repl_channel(self):
        reset_ha_metrics_for_tests()
        standby = TokenServer(_service(), port=0, standby_of="primary")
        standby.start()
        primary = TokenServer(
            _service(), port=0,
            replicate_to=[("127.0.0.1", standby.port)],
            repl_interval_ms=50,
        )
        primary.start()
        fc = FailoverTokenClient(
            [("127.0.0.1", primary.port), ("127.0.0.1", standby.port)],
            failure_threshold=3, deadline_ms=2000,
        )
        try:
            # chaos on the wire: resets + delay hit the repl channel (and
            # everything else). Invariant: every client request RESOLVES.
            chaos.arm("conn_reset:p=0.02;lane_delay:p=0.2,ms=2", seed=SEED)
            served = 0
            for _ in range(40):
                r = fc.request_token(1)
                assert r is not None
                assert r.status in (
                    TokenStatus.OK, TokenStatus.BLOCKED,
                    TokenStatus.SHOULD_WAIT,
                )
                served += 1
            assert served == 40
            # deterministic settle: disarm, then let the final delta ship
            chaos.disarm()
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                p = primary.service.metrics_snapshot()
                s = standby.service.metrics_snapshot()
                if s and p and s[1]["pass_qps"] == p[1]["pass_qps"]:
                    break
                time.sleep(0.05)
            else:
                pytest.fail(f"standby never converged: {p} vs {s}")
            # repl channel survived the chaos: deltas ship and apply (a
            # snapshot may have subsumed the traffic; heartbeat deltas tick
            # every interval regardless, so one lands within the deadline)
            deadline = time.monotonic() + 5.0
            repl = ha_metrics().snapshot()["replication"]
            while (repl["events"].get("shipped", 0) < 1
                   and time.monotonic() < deadline):
                time.sleep(0.05)
                repl = ha_metrics().snapshot()["replication"]
            assert repl["events"].get("shipped", 0) >= 1
            # primary dies; promotion opens the door; the client walks over
            primary.stop()
            assert standby.promote(reason="test")
            assert not standby.is_standby
            for _ in range(10):
                r = fc.request_token(1)
                assert r is not None and r.status in (
                    TokenStatus.OK, TokenStatus.BLOCKED,
                    TokenStatus.SHOULD_WAIT,
                )
        finally:
            chaos.disarm()
            fc.close()
            primary.stop()
            standby.stop()

    def test_unpromoted_standby_refuses_with_standby_status(self):
        standby = TokenServer(_service(), port=0, standby_of="primary")
        standby.start()
        try:
            client = TokenClient("127.0.0.1", standby.port)
            r = client.request_token(1)
            assert r.status == TokenStatus.STANDBY
            assert client.ping()  # standbys stay pingable
            client.close()
            standby.promote(reason="test")
            client = TokenClient("127.0.0.1", standby.port)
            assert client.request_token(1).status == TokenStatus.OK
            client.close()
        finally:
            standby.stop()

    def test_watchdog_auto_promotes_on_primary_silence(self):
        standby = TokenServer(
            _service(), port=0, standby_of="primary",
            promote_after_ms=200,
        )
        standby.start()
        try:
            # no contact yet → death undetectable → no premature promotion
            # even after the timer would have elapsed (slow-booting primary)
            time.sleep(0.5)
            assert standby.is_standby
            # one HELLO-equivalent contact arms the silence timer
            standby.applier._touch()
            deadline = time.monotonic() + 5.0
            while standby.is_standby and time.monotonic() < deadline:
                time.sleep(0.05)
            assert not standby.is_standby, "watchdog never promoted"
        finally:
            standby.stop()


# -- satellite: torn snapshot artifacts --------------------------------------
class TestSnapshotTornWrite:
    def test_torn_newest_artifact_falls_back(self, tmp_path):
        from sentinel_tpu.core import clock as _clock
        from sentinel_tpu.ha.snapshot import load_latest, save_snapshot

        donor = _service()
        donor.request_token(1)
        p1 = save_snapshot(donor, str(tmp_path))
        time.sleep(0.002)  # distinct saved_at_ms artifact names
        p2 = save_snapshot(donor, str(tmp_path))
        assert p1 != p2
        good = json.load(open(p1))
        # simulate a torn write surviving a crash under the final name
        with open(p2, "w") as f:
            f.write(open(p1).read()[: 40])
        doc = load_latest(str(tmp_path))
        assert doc is not None and doc == good

    def test_all_torn_restores_nothing(self, tmp_path):
        from sentinel_tpu.ha.snapshot import load_latest, save_snapshot

        donor = _service()
        path = save_snapshot(donor, str(tmp_path))
        with open(path, "w") as f:
            f.write("{\"truncated\": ")
        assert load_latest(str(tmp_path)) is None


# -- satellite: datasource refresh backoff -----------------------------------
class TestDatasourceBackoff:
    def test_failed_parse_retains_last_known_good(self):
        from sentinel_tpu.datasource.base import (
            ReadableDataSource,
            refresh_failure_totals,
            reset_refresh_failures_for_tests,
        )

        reset_refresh_failures_for_tests()

        class Src(ReadableDataSource):
            def __init__(self):
                super().__init__(converter=lambda s: json.loads(s))
                self.raw = '["rule-a"]'

            def read_source(self):
                return self.raw

        src = Src()
        assert src.refresh() is True
        assert src.property.value == ["rule-a"]
        src.raw = '{"truncated'  # torn mid-write
        assert src.refresh() is False
        assert src.property.value == ["rule-a"], "stale beats none"
        src.raw = "null"  # parses, but to nothing
        assert src.refresh() is False
        assert src.property.value == ["rule-a"]
        assert refresh_failure_totals().get("Src", 0) == 2

    def test_poll_interval_backs_off_and_caps(self):
        from sentinel_tpu.datasource.base import AutoRefreshDataSource

        src = AutoRefreshDataSource(converter=lambda s: s,
                                    refresh_interval_s=1.0)
        assert src._poll_interval_s() == 1.0
        src._consecutive_failures = 2
        assert src._poll_interval_s() == 4.0
        src._consecutive_failures = 30
        assert src._poll_interval_s() == 10.0  # capped at 10×
        src._consecutive_failures = 0
        assert src._poll_interval_s() == 1.0

    def test_loop_counts_consecutive_failures(self):
        from sentinel_tpu.datasource.base import AutoRefreshDataSource

        boom = AutoRefreshDataSource(
            converter=lambda s: s, refresh_interval_s=0.01
        )
        boom.read_source = lambda: (_ for _ in ()).throw(IOError("down"))
        boom.start()
        try:
            deadline = time.monotonic() + 2.0
            while (boom._consecutive_failures < 2
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert boom._consecutive_failures >= 2
        finally:
            boom.close()


# -- satellite: heartbeat backoff --------------------------------------------
class TestHeartbeatBackoff:
    def test_interval_backs_off_with_jitter_and_resets(self):
        from sentinel_tpu.transport.heartbeat import HeartbeatSender

        hb = HeartbeatSender(
            dashboard_addrs=["127.0.0.1:1"], interval_ms=1000
        )
        assert hb._interval_s() == 1.0  # healthy: exact cadence
        hb._consecutive_failures = 1
        for _ in range(20):
            assert 2.0 * 0.75 <= hb._interval_s() <= 2.0 * 1.25
        hb._consecutive_failures = 50
        for _ in range(20):
            assert 10.0 * 0.75 <= hb._interval_s() <= 10.0 * 1.25  # capped
        hb._consecutive_failures = 0
        assert hb._interval_s() == 1.0

    def test_loop_resets_on_success(self):
        from sentinel_tpu.transport.heartbeat import HeartbeatSender

        hb = HeartbeatSender(dashboard_addrs=["x"], interval_ms=10)
        hb.send_once = lambda: True
        hb._consecutive_failures = 5
        hb._stop.clear()
        import threading

        t = threading.Thread(target=hb._loop, daemon=True)
        t.start()
        deadline = time.monotonic() + 2.0
        while hb._consecutive_failures and time.monotonic() < deadline:
            time.sleep(0.01)
        hb.stop()
        assert hb._consecutive_failures == 0
