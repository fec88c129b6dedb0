"""The plain reference against windows worked out by hand."""

from cellbench.families import flow as deploy, flow_reference as reference
from cellbench.deploy import (BLOCKED, DEFAULT, NO_RULE, OK, RATE_LIMITER,
                              SHOULD_WAIT, TOO_MANY)


def ref(**kw):
    rules = {1: (20.0, "a", DEFAULT), 2: (100.0, "a", RATE_LIMITER),
             3: (5000.0, "b", DEFAULT)}
    return reference.Reference(rules, 30000.0, 100, 10, **kw)


def test_count_20_sent_30_with_acquire_3():
    status, _ = ref().decide_frame(0, [1] * 30, [3] * 30)
    assert status == [OK] * 6 + [BLOCKED] * 24  # 6 x 3 = 18, 21 > 20


def test_window_slides_bucket_by_bucket():
    r = ref()
    assert r.decide_frame(1000, [1] * 25, [1] * 25)[0].count(OK) == 20
    # 999 ms later the bucket that began at 1000 is still the oldest of ten
    assert r.decide(1999, 1, 1)[0] == BLOCKED
    # at 2000 it has left the window: all 20 tokens are back
    assert r.decide_frame(2000, [1] * 21, [1] * 21)[0].count(OK) == 20


def test_tokens_spread_over_buckets_leave_one_bucket_at_a_time():
    r = ref()
    for k in range(10):  # 2 tokens in each of ten buckets
        assert r.decide_frame(k * 100, [1, 1], [1, 1])[0] == [OK, OK]
    assert r.decide(950, 1, 1)[0] == BLOCKED
    assert r.decide_frame(1000, [1] * 3, [1] * 3)[0] == [OK, OK, BLOCKED]


def test_count_5000_sent_6000_is_exact_and_the_control_is_not():
    status, _ = ref().decide_frame(0, [3] * 6000, [1] * 6000)
    assert status == [OK] * 5000 + [BLOCKED] * 1000
    low, _ = ref(lower_precision=True).decide_frame(0, [3] * 6000, [1] * 6000)
    assert low.count(OK) != 5000


def test_guard_counts_requests_not_tokens_and_refuses_before_the_flow():
    r = ref()
    status, _ = r.decide_frame(0, [3] * 32768, [1] * 32768)
    assert status.count(TOO_MANY) == 2768
    assert status.count(OK) == 5000  # the flow's own count still holds
    assert r.decide(0, 99, 1)[0] == NO_RULE


def test_pacing_first_row_now_then_waits_then_blocked():
    status, wait = ref().decide_frame(0, [2] * 60, [1] * 60)
    assert status == [OK] + [SHOULD_WAIT] * 50 + [BLOCKED] * 9
    assert wait[:51] == [10 * j for j in range(51)]


def test_deployment_rules_cover_every_flow():
    dep = deploy.load_deployment("mesh-100k")
    rules = list(dep.rules())
    assert len(rules) == dep.n_flows == 100_000
    assert len({r[0] for r in rules}) == 100_000
    by_id = {r[0]: r for r in rules}
    assert by_id[0][1] == 4000 and by_id[64][1] == 2000
    assert by_id[256][1] == 1e9 and by_id[3 * 64 + 5][1] == 500
    assert by_id[deploy.PROBE_BASE][2] == "ns62"
