"""Cluster concurrency (semaphore) mode: the semantics, stated once and held
against both what serves them (``DefaultTokenService``'s plane on the device,
``engine/concurrent.py``) and their plain reference
(``cellbench/families/concurrent_reference.py``).

Mirrors the reference's ``ConcurrentClusterFlowCheckerTest`` /
``CurrentConcurrencyManagerTest`` / ``TokenCacheNodeManagerTest`` strategy:
checker semantics with an explicit clock, expiry without real sleeps, and
(beyond the reference) one wire-level round-trip test. Until PR 41 these
cases drove ``ConcurrencyManager``, the Python dict the service then served
from; the three that timed that dict's sweep budget (its amortized sweep per
acquire, its bounded scan behind a wall of long-lived tokens, its thread's
lifecycle) went with it: ``tests/test_concurrent_batch.py`` holds their
successors (the ring's scan, two timeouts in one table, the timer).
"""

import os
import sys

import pytest

from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.concurrent import ConcurrentFlowRule
from sentinel_tpu.cluster.server import TokenServer
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import EngineConfig, TokenStatus
from sentinel_tpu.engine.rules import ThresholdMode

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cellbench.families import concurrent_reference as R  # noqa: E402

RULES = [
    ConcurrentFlowRule(flow_id=1, concurrency_level=3),
    ConcurrentFlowRule(flow_id=2, concurrency_level=2,
                       mode=ThresholdMode.AVG_LOCAL),
    ConcurrentFlowRule(flow_id=3, concurrency_level=5,
                       resource_timeout_ms=100),
    ConcurrentFlowRule(flow_id=4, concurrency_level=10,
                       resource_timeout_ms=1000),
]


class Served:
    """The service's plane, one row a call, on the test's clock."""

    def __init__(self, clock):
        self.clock = clock
        self.svc = DefaultTokenService(
            EngineConfig(max_flows=8, max_namespaces=2, batch_size=8),
            serve_buckets=(8,), concurrent_max_tokens=64)
        self.svc.load_concurrent_rules(RULES)
        self.svc.close()  # no timer: expiry runs when the test says

    def acquire(self, flow, count=1):
        r = self.svc.request_concurrent_token(flow, count)
        return int(r.status), r.remaining, r.token_id

    def release(self, token):
        return int(self.svc.release_concurrent_token(token).status)

    def expire(self):
        return self.svc.concurrent_tick()

    def held(self, flow):
        return self.svc.concurrent_stats()["held"][flow]

    def connected(self, n):
        self.svc.connected_count_changed("default", n)


class Plain:
    """The plain reference, on the same clock."""

    def __init__(self, clock):
        self.clock, self.n = clock, 1
        self.ref = R.Reference({}, {r.flow_id: r.resource_timeout_ms
                                    for r in RULES})
        self.connected(1)

    def now(self):
        return self.clock.now_ms() - 1_700_000_000_000

    def acquire(self, flow, count=1):
        return self.ref.acquire(self.now(), flow, count)

    def release(self, token):
        return self.ref.release(token)

    def expire(self):
        return self.ref.expire(self.now())

    def held(self, flow):
        return self.ref.held.get(flow, 0)

    def connected(self, n):
        self.ref.levels = {
            r.flow_id: r.concurrency_level * (
                1 if r.mode == ThresholdMode.GLOBAL else n) for r in RULES}


OK, BLOCKED, NO_RULE = 0, 1, 3
RELEASE_OK, ALREADY = 6, 7


@pytest.fixture(params=[Served, Plain], ids=["served", "reference"])
def sem(request, manual_clock):
    return request.param(manual_clock)


class TestAcquireRelease:
    def test_admit_up_to_level_then_block(self, sem):
        results = [sem.acquire(1) for _ in range(4)]
        assert [r[0] for r in results] == [OK] * 3 + [BLOCKED]
        assert sem.held(1) == 3
        assert results[0][1] == 2 and results[2][1] == 0
        assert len({r[2] for r in results[:3]}) == 3 and results[3][2] == 0

    def test_release_frees_permit(self, sem):
        _s, _r, token = sem.acquire(1)
        assert sem.release(token) == RELEASE_OK
        assert sem.held(1) == 0
        assert sem.acquire(1)[0] == OK

    def test_double_release_is_idempotent(self, sem):
        _s, _r, token = sem.acquire(1)
        assert sem.release(token) == RELEASE_OK
        assert sem.release(token) == ALREADY
        assert sem.held(1) == 0  # no double decrement

    def test_weighted_acquire(self, sem):
        assert sem.acquire(1, 2)[0] == OK
        assert sem.acquire(1, 2)[0] == BLOCKED
        assert sem.acquire(1, 1)[0] == OK

    def test_no_rule(self, sem):
        assert sem.acquire(99) == (NO_RULE, 0, 0)

    def test_avg_local_scales_with_connected_count(self, sem):
        # level 2 × 3 clients = 6 permits
        sem.connected(3)
        results = [sem.acquire(2) for _ in range(7)]
        assert sum(r[0] == OK for r in results) == 6
        assert results[6][0] == BLOCKED


class TestExpiry:
    def test_expired_tokens_reclaimed(self, sem):
        for _ in range(5):
            assert sem.acquire(3)[0] == OK
        assert sem.acquire(3)[0] == BLOCKED
        # resource_timeout_ms=100: not at 99 ms, all by 100
        sem.clock.advance(99)
        assert sem.expire() == 0
        sem.clock.advance(1)
        assert sem.expire() == 5
        assert sem.held(3) == 0
        assert sem.acquire(3)[0] == OK

    def test_release_after_expiry_reports_already_release(self, sem):
        _s, _r, token = sem.acquire(3)
        sem.clock.advance(200)
        sem.expire()
        assert sem.release(token) == ALREADY
        assert sem.held(3) == 0

    def test_a_token_past_its_time_can_be_released_until_it_is_reclaimed(
            self, sem):
        _s, _r, token = sem.acquire(3)
        sem.clock.advance(200)  # expiry has not run yet
        assert sem.release(token) == RELEASE_OK
        assert sem.expire() == 0 and sem.held(3) == 0

    def test_mixed_ttls_expire_each_at_its_own_time(self, sem):
        sem.acquire(4)  # long TTL issued first
        sem.acquire(3)  # short TTL second
        sem.clock.advance(100)
        assert sem.expire() == 1  # only flow 3's token expired
        assert sem.held(4) == 1 and sem.held(3) == 0
        sem.clock.advance(900)
        assert sem.expire() == 1 and sem.held(4) == 0


class TestWire:
    def test_acquire_release_over_socket(self):
        svc = DefaultTokenService(EngineConfig(max_flows=8, max_namespaces=2, batch_size=8))
        svc.load_concurrent_rules([ConcurrentFlowRule(flow_id=7, concurrency_level=2)])
        server = TokenServer(svc, port=0, batch_window_ms=0.5)
        server.start()
        client = TokenClient("127.0.0.1", server.port, timeout_ms=2000)
        try:
            r1 = client.request_concurrent_token(7)
            r2 = client.request_concurrent_token(7)
            r3 = client.request_concurrent_token(7)
            assert r1.ok and r2.ok
            assert r1.token_id > 0 and r1.token_id != r2.token_id
            assert r3.status == TokenStatus.BLOCKED
            assert client.release_concurrent_token(r1.token_id).status == TokenStatus.RELEASE_OK
            assert client.request_concurrent_token(7).ok
            assert client.release_concurrent_token(r1.token_id).status == TokenStatus.ALREADY_RELEASE
        finally:
            client.close()
            server.stop()
            svc.close()
