"""Wire-rev-5 token leasing (perf tentpole): conservation end to end.

Server side: a grant pre-pays the slice into the LEASED window column (so
decide-path occupancy and every psum'd replica already account delegated
tokens), return/renew credit the EXACT grant bucket only while its start
stamp still matches, TTL expiry revokes, snapshot/restore and live MOVE
carry the charge while recalling the registry. Client side: hot flows
admit locally from the cached slice, every refusal falls back to the
per-request wire path, and close() returns unused tokens early.
"""

import threading
import time

import numpy as np
import pytest

from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.server import TokenServer
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig, TokenStatus
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.engine.state import flow_spec

G = ThresholdMode.GLOBAL
# default window: 10 x 100ms buckets -> threshold == rule count per window
CFG = EngineConfig(max_flows=64, max_namespaces=8, batch_size=64)
FLOW = 101


def _svc(count=50.0, ns="default", **kw):
    svc = DefaultTokenService(CFG, **kw)
    svc.load_rules([ClusterFlowRule(FLOW, count, G, ns)])
    return svc


def _drain(svc, flow=FLOW):
    """Admit until BLOCKED; returns how many decisions passed — the flow's
    remaining window headroom as the decide kernel sees it."""
    passed = 0
    while svc.request_token(flow).ok:
        passed += 1
        assert passed <= 1000, "window never closed"
    return passed


# -- server conservation ------------------------------------------------------
class TestServerLease:
    def test_grant_charges_window_and_decide_sees_it(self, manual_clock):
        svc = _svc()
        r = svc.lease_grant(FLOW, want=20)
        assert r.ok and r.tokens == 20 and r.lease_id > 0 and r.ttl_ms > 0
        # the 20 delegated tokens occupy the window NOW (charge-at-grant):
        # only 30 of the 50 window tokens remain for the decision path
        assert _drain(svc) == 30
        assert svc.outstanding_leases() == 20

    def test_return_credits_unused_tokens_back(self, manual_clock):
        svc = _svc()
        r = svc.lease_grant(FLOW, want=20)
        assert svc.lease_return(r.lease_id, used=5).ok
        # 15 unused came back; only the 5 actually spent stay charged
        assert _drain(svc) == 45
        assert svc.outstanding_leases() == 0
        assert svc.lease_stats()["returned"] == 1

    def test_return_is_idempotent_for_unknown_lease(self, manual_clock):
        svc = _svc()
        assert svc.lease_return(424242, used=7).ok
        assert _drain(svc) == 50

    def test_renew_credits_then_regrants_atomically(self, manual_clock):
        svc = _svc()
        a = svc.lease_grant(FLOW, want=20)
        b = svc.lease_renew(a.lease_id, FLOW, used=5, want=20)
        assert b.ok and b.lease_id != a.lease_id
        # credit first (LEASED 20 -> 5), then grant against the freed
        # headroom: min(20, 0.5 * (50 - 5)) = 20
        assert b.tokens == 20
        assert _drain(svc) == 50 - 5 - 20
        assert svc.outstanding_leases() == 20

    def test_credit_requires_the_exact_grant_bucket(self, manual_clock):
        # TTL far beyond the window so rotation (not expiry) is what's
        # being exercised
        svc = _svc(lease_ttl_ms=600_000)
        spec = flow_spec(CFG)
        r = svc.lease_grant(FLOW, want=20)
        manual_clock.advance(spec.bucket_ms * spec.n_buckets + 1)
        assert svc.lease_return(r.lease_id, used=5).ok
        # the grant bucket rotated out, taking the charge with it; the
        # credit MUST be dropped (not applied to some newer bucket), or the
        # window sum would go net negative and over-admit
        assert _drain(svc) == 50

    def test_fraction_caps_grant_and_headroom_refuses(self, manual_clock):
        svc = _svc()
        a = svc.lease_grant(FLOW, want=1000)
        assert a.tokens == 25  # lease_fraction 0.5 of the 50-token window
        b = svc.lease_grant(FLOW, want=1000)
        assert b.tokens == 12  # half of what the first grant left
        got, last = a.tokens + b.tokens, b
        while True:
            last = svc.lease_grant(FLOW, want=1000)
            if not last.ok:
                break
            got += last.tokens
        assert last.status == int(TokenStatus.NOT_LEASABLE)
        assert got + _drain(svc) == 50  # delegated + direct == the window

    def test_zero_want_and_unknown_flow_refused(self, manual_clock):
        svc = _svc()
        assert svc.lease_grant(FLOW, 0).status == int(
            TokenStatus.NOT_LEASABLE)
        assert svc.lease_grant(777, 8).status == int(
            TokenStatus.NO_RULE_EXISTS)

    def test_disabled_by_fraction_zero(self, manual_clock):
        svc = _svc(lease_fraction=0.0)
        assert svc.lease_grant(FLOW, 8).status == int(
            TokenStatus.NOT_LEASABLE)

    def test_ttl_expiry_revokes_and_renew_degrades_to_grant(
        self, manual_clock
    ):
        svc = _svc(lease_ttl_ms=500)
        a = svc.lease_grant(FLOW, want=20)
        manual_clock.advance(600)
        assert svc.outstanding_leases() == 0
        assert svc.lease_stats()["revoked"] == 1
        # renewing the dead lease is a credit-less grant: the old charge
        # stays in the window (a dead client may have spent all of it —
        # the conservative assumption) and a fresh slice is cut
        b = svc.lease_renew(a.lease_id, FLOW, used=20, want=10)
        assert b.ok and b.tokens == 10
        assert _drain(svc) == 50 - 20 - 10

    def test_stats_and_outstanding_gauges(self, manual_clock):
        svc = _svc()
        a = svc.lease_grant(FLOW, want=10)
        svc.lease_renew(a.lease_id, FLOW, used=10, want=10)
        s = svc.lease_stats()
        assert s["granted"] == 1 and s["renewed"] == 1
        assert s["outstanding"] == 1 and s["outstanding_tokens"] == 10


# -- failover + rebalance conservation ----------------------------------------
class TestLeaseStateMotion:
    def test_snapshot_restore_carries_charge_not_registry(
        self, manual_clock
    ):
        donor = _svc()
        donor.lease_grant(FLOW, want=20)
        heir = DefaultTokenService(CFG)
        heir.import_state(donor.export_state())
        # the LEASED charge replicated bit-equal with the window state...
        d = np.asarray(donor.export_state()["flow"]["counts"])
        h = np.asarray(heir.export_state()["flow"]["counts"])
        assert np.array_equal(d, h)
        # ...so the heir admits exactly what the donor would have
        assert _drain(heir) == 30
        # but the lease registry is host state and deliberately NOT
        # replicated: a promoted standby starts with zero outstanding and
        # serves renews as credit-less grants (see lease_renew)
        assert heir.outstanding_leases() == 0

    def test_move_transfers_charge_and_recalls_leases(self, manual_clock):
        ns = "mv-lease"
        src = DefaultTokenService(CFG)
        src.load_namespace_rules(ns, [ClusterFlowRule(11, 50.0, G, ns)])
        a = src.lease_grant(11, want=20)
        assert a.ok
        src.begin_move(ns, "10.0.0.9:1234", 3)
        # recall: the registry entry dies with the move; renew and grant
        # answer MOVED so clients re-grant at the destination
        assert src.outstanding_leases() == 0
        assert src.lease_stats()["revoked"] == 1
        r = src.lease_renew(a.lease_id, 11, used=5, want=20)
        assert r.status == int(TokenStatus.MOVED)
        assert r.endpoint == "10.0.0.9:1234" and r.tokens == 3
        assert src.lease_grant(11, 8).status == int(TokenStatus.MOVED)
        # transfer: the LEASED charge rides the namespace export — the
        # destination's window already owes the delegated 20 tokens
        doc = src.export_namespace_state(ns)
        dst = DefaultTokenService(CFG)
        dst.import_namespace_state(doc)
        assert _drain(dst, 11) == 30
        # and the same doc folds back losslessly on abort at the source
        src.abort_move(ns)
        assert _drain(src, 11) == 30


# -- client-local admission over a live front door ----------------------------
class TestClientLease:
    @pytest.fixture()
    def served(self):
        # real wall clock: the client's lease cache runs on time.monotonic.
        # TTL sized far beyond the test so only explicit paths end a lease.
        svc = DefaultTokenService(
            EngineConfig(max_flows=16, max_namespaces=4, batch_size=64),
            lease_ttl_ms=60_000,
        )
        svc.load_rules([ClusterFlowRule(1, 1e9, G)])
        server = TokenServer(svc, port=0)
        server.start()
        yield svc, server
        server.stop()
        svc.close()

    def test_local_admission_amortizes_rpcs(self, served):
        svc, server = served
        # lease_want=128: the renew-ahead fires when half the slice is spent
        # (64 tokens) or at 45 % of the 60 s TTL, so the first 40 admissions
        # reach neither and nothing runs behind the test's back
        c = TokenClient("127.0.0.1", server.port, timeout_ms=3000,
                        lease=True, lease_want=128)
        try:
            assert c.ping()  # connect now: the handshake is an RPC too
            base = c.lease_stats()["rpcs"]
            for _ in range(40):
                assert c.request_token(1).ok
            s = c.lease_stats()
            # one synchronous grant; everything else never touched the wire
            assert s["granted"] == 1 and s["renewed"] == 0
            assert s["local_admits"] == 40
            assert s["wire_rows"] == 0
            assert s["rpcs"] == base + 1
            # the 64th token is the renew-ahead point: one background
            # renew, waited for here, and still no decision on the wire
            for _ in range(24):
                assert c.request_token(1).ok
            for t in threading.enumerate():
                if t.name == "sentinel-lease-renew":
                    t.join(timeout=10)
            assert c.request_token(1).ok  # from the fresh slice
            s = c.lease_stats()
            assert s["granted"] == 1 and s["renewed"] == 1
            assert s["local_admits"] == 65
            assert s["wire_rows"] == 0
            assert s["rpcs"] == base + 2
        finally:
            c.close()

    def test_refusal_falls_back_to_wire_decisions(self):
        svc = DefaultTokenService(
            EngineConfig(max_flows=16, max_namespaces=4, batch_size=64),
            lease_fraction=0.0,  # leasing disabled server-side
        )
        svc.load_rules([ClusterFlowRule(1, 1e9, G)])
        server = TokenServer(svc, port=0)
        server.start()
        c = TokenClient("127.0.0.1", server.port, timeout_ms=3000,
                        lease=True, lease_want=64)
        try:
            for _ in range(10):
                assert c.request_token(1).ok  # NOT_LEASABLE never loses a verdict
            s = c.lease_stats()
            assert s["refused"] >= 1
            assert s["local_admits"] == 0
            assert s["wire_rows"] == 10
        finally:
            c.close()
            server.stop()
            svc.close()

    def test_close_returns_the_unused_slice(self, served):
        svc, server = served
        c = TokenClient("127.0.0.1", server.port, timeout_ms=3000,
                        lease=True, lease_want=64)
        for _ in range(5):
            assert c.request_token(1).ok
        time.sleep(0.2)  # let any renew-ahead thread settle
        c.close()
        assert c.lease_stats()["returned"] >= 1
        s = svc.lease_stats()
        assert s["outstanding"] == 0 and s["outstanding_tokens"] == 0
        assert s["returned"] >= 1
