"""The breaker family's plain reference (``cellbench/families/
breaker_reference.py``) by hand, and ``DefaultTokenService`` against it on
seeded rows: the three ``DegradeStrategy``s, trip / probe / recover / reopen,
strict ``>``, ``min_request_amount``, the fence after a close, the control
with 8-bit totals; the cause of
ROADMAP Reach A1 (a report's clock read before its step compiled); the warm
outcome step; what the decide step's breaker arm and the outcome step say
they did."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from cellbench.deploy import (BLOCKED, DEFAULT, DEGRADED,  # noqa: E402
                              NO_RULE, OK)
from cellbench.families import breaker_reference as BR  # noqa: E402
from sentinel_tpu.cluster.token_service import (  # noqa: E402
    DefaultTokenService)
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig  # noqa: E402
from sentinel_tpu.engine.rules import (  # noqa: E402
    DegradeRule, DegradeStrategy, ThresholdMode)
from sentinel_tpu.metrics.server import server_metrics  # noqa: E402

S, R, C = BR.SLOW_REQUEST_RATIO, BR.ERROR_RATIO, BR.ERROR_COUNT
REC = 2000  # recovery timeout of every breaker here, ms


def breakers():
    return {1: BR.Breaker(S, 0.6, slow_rt_ms=50, recovery_timeout_ms=REC),
            2: BR.Breaker(R, 0.5, recovery_timeout_ms=REC),
            3: BR.Breaker(C, 5, recovery_timeout_ms=REC)}


def ref(**control):
    rules = {f: (1e9, "a", DEFAULT) for f in (1, 2, 3, 4)}
    rules[5] = (3.0, "a", DEFAULT)  # a metered flow without a breaker
    return BR.Reference(rules, breakers(), 30000.0, 100, 10, **control)


def ask(r, t, flow, n=1):
    return r.decide_frame(t, [flow] * n, [1] * n)


# -- the reference by hand ------------------------------------------------------
@pytest.mark.parametrize("flow, at, past", [
    # SLOW_REQUEST_RATIO 0.6: 6 of 10 slow is on the threshold, 7 is past it
    (1, ([51] * 6 + [50] * 4, [0] * 10), ([51] * 7 + [50] * 3, [0] * 10)),
    # ERROR_RATIO 0.5
    (2, ([5] * 10, [1] * 5 + [0] * 5), ([5] * 10, [1] * 6 + [0] * 4)),
    # ERROR_COUNT 5
    (3, ([5] * 10, [1] * 5 + [0] * 5), ([5] * 10, [1] * 6 + [0] * 4)),
])
def test_a_threshold_is_passed_strictly(flow, at, past):
    r = ref()
    r.report(1000, [flow] * 10, *at)
    assert ask(r, 1050, flow, 3) == ([OK] * 3, [0] * 3)
    r = ref()
    r.report(1000, [flow] * 10, *past)
    assert ask(r, 1050, flow, 3) == ([DEGRADED] * 3, [REC] * 3)


def test_under_min_request_amount_nothing_trips():
    r = ref()
    r.report(1000, [3] * 4, [5] * 4, [1] * 4)  # 4 errors of 4: under 5 calls
    r.report(1000, [2] * 4, [5] * 4, [1] * 4)
    assert ask(r, 1010, 3)[0] == ask(r, 1010, 2)[0] == [OK]
    r.report(1020, [2], [5], [0])  # the fifth completion: 4 of 5 > 0.5
    assert ask(r, 1030, 2)[0] == [DEGRADED]
    r.report(1020, [3] * 2, [5] * 2, [1] * 2)  # 6 errors > 5
    assert ask(r, 1030, 3)[0] == [DEGRADED]


def test_a_completion_leaves_with_its_bucket():
    r = ref()
    r.report(1050, [3] * 8, [5] * 8, [1] * 8)
    # the bucket of 1000 is read while it starts after t - 1000
    assert ask(ref_copy(r), 1999, 3)[0] == [DEGRADED]
    assert ask(ref_copy(r), 2000, 3)[0] == [OK]


def ref_copy(r):
    import copy

    return copy.deepcopy(r)


def test_open_sheds_until_the_timeout_then_one_probe_passes():
    r = ref()
    r.report(1000, [3] * 8, [5] * 8, [1] * 8)
    assert ask(r, 1100, 3, 2) == ([DEGRADED] * 2, [REC] * 2)  # trips here
    assert ask(r, 1600, 3) == ([DEGRADED], [REC - 500])
    assert ask(r, 1100 + REC - 1, 3) == ([DEGRADED], [1])
    # the timeout has gone by: the first row is the probe, the rest wait
    status, retry = ask(r, 1100 + REC, 3, 4)
    assert status == [OK] + [DEGRADED] * 3 and retry == [0] + [REC] * 3
    # until a completion resolves it every row is shed, by the ticket's clock
    assert ask(r, 1100 + REC + 300, 3) == ([DEGRADED], [REC - 300])


def test_a_good_completion_closes_and_fences_what_came_before():
    r = ref()
    r.report(1000, [3] * 8, [5] * 8, [1] * 8)
    ask(r, 1100, 3)
    ask(r, 1100 + REC, 3)  # the probe
    t = 1100 + REC + 40
    r.report(t, [3] * 7, [5] * 7, [0] + [1] * 6)  # the first one decides
    assert r.machines[3].state == BR.CLOSED and r.machines[3].since == t
    # six errors lie in the bucket of the close, before the fence: not read
    assert ask(r, t + 20, 3)[0] == [OK]
    # the same six in a later bucket are read
    r.report(t + 100, [3] * 6, [5] * 6, [1] * 6)
    assert ask(r, t + 110, 3)[0] == [DEGRADED]


def test_a_bad_completion_opens_again_for_the_whole_timeout():
    r = ref()
    r.report(1000, [1] * 8, [400] * 8, [0] * 8)  # slow, no exception
    ask(r, 1100, 1)
    ask(r, 1100 + REC, 1)
    t = 1100 + REC + 40
    r.report(t, [1], [51], [0])  # SLOW_REQUEST_RATIO judges the rt
    assert r.machines[1].state == BR.OPEN
    assert ask(r, t + 10, 1) == ([DEGRADED], [REC - 10])
    assert ask(r, t + REC, 1, 2)[0] == [OK, DEGRADED]
    r.report(t + REC + 5, [1], [50], [1])  # 50 ms is not slow: closes
    assert ask(r, t + REC + 10, 1, 2)[0] == [OK, OK]


def test_a_probe_nobody_reports_on_is_given_again():
    r = ref()
    r.report(1000, [2] * 8, [5] * 8, [1] * 8)
    ask(r, 1100, 2)
    assert ask(r, 1100 + REC, 2)[0] == [OK]
    assert ask(r, 1100 + 2 * REC - 1, 2)[0] == [DEGRADED]
    assert ask(r, 1100 + 2 * REC, 2, 2)[0] == [OK, DEGRADED]


def test_flows_without_a_breaker_and_the_window_under_it():
    r = ref()
    r.report(1000, [4, 5, 9], [900] * 3, [1] * 3)
    assert r.reported == 2  # flow 9 has no rule: dropped by the server
    r.report(1000, [4] * 20, [900] * 20, [1] * 20)
    assert ask(r, 1010, 4, 3)[0] == [OK] * 3
    assert r.decide_frame(1010, [5] * 4 + [9], [1] * 5)[0] == [
        OK, OK, OK, BLOCKED, NO_RULE]


def test_eight_bit_totals_depart_from_the_reference():
    """The control: 257 errors of 513 calls are over a half; rounded to 8
    significant bits they are 256 of 512, which is not. The window under the
    breakers goes wrong past 256 too."""
    got = []
    for r in (ref(), ref(lower_precision=True)):
        r.report(1000, [2] * 513, [5] * 513, [1] * 257 + [0] * 256)
        got.append(ask(r, 1010, 2)[0])
    assert got == [[DEGRADED], [OK]]
    sound, rounded = ref(), ref(lower_precision=True)
    sound.rules[5] = rounded.rules[5] = (300.0, "a", DEFAULT)
    want = sound.decide_frame(1000, [5] * 320, [1] * 320)[0]
    assert want == [OK] * 300 + [BLOCKED] * 20
    assert rounded.decide_frame(1000, [5] * 320, [1] * 320)[0] != want


def test_the_reference_counts_its_transitions():
    r = ref()
    r.report(1000, [3] * 8, [5] * 8, [1] * 8)
    ask(r, 1100, 3)  # trips
    ask(r, 1100 + REC, 3)  # the probe
    r.report(1100 + REC + 10, [3], [5], [1])  # rolled back
    ask(r, 1100 + 2 * REC + 10, 3)  # the second probe
    r.report(1100 + 2 * REC + 20, [3], [5], [0])  # closed
    assert r.moves == {"open": 1, "probe": 2, "close": 1, "rollback": 1}


# -- the service against the reference -------------------------------------------
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=256)
T0 = 1_700_000_000_000


def service(clock, with_breakers=True, warm=True):
    clock.set_ms(T0)
    svc = DefaultTokenService(CFG, serve_buckets=(64, 256), fuse_depths=())
    svc.load_rules(
        [ClusterFlowRule(f, 1e9, ThresholdMode.GLOBAL, "a")
         for f in (1, 2, 3, 4)]
        + [ClusterFlowRule(5, 3.0, ThresholdMode.GLOBAL, "a")])
    if with_breakers:
        svc.load_degrade_rules([
            DegradeRule(1, DegradeStrategy.SLOW_REQUEST_RATIO, 0.6,
                        slow_rt_ms=50, recovery_timeout_ms=REC,
                        namespace="a"),
            DegradeRule(2, DegradeStrategy.ERROR_RATIO, 0.5,
                        recovery_timeout_ms=REC, namespace="a"),
            DegradeRule(3, DegradeStrategy.ERROR_COUNT, 5,
                        recovery_timeout_ms=REC, namespace="a")])
    if warm:
        svc.warmup()  # the engine's clock starts here: T0 is its ms 1
    return svc


def at(clock, t_ms: int) -> None:
    """Set the wall clock so that the engine reads ``t_ms``."""
    clock.set_ms(T0 - 1 + t_ms)


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_the_service_answers_as_the_reference_on_seeded_rows(manual_clock,
                                                             seed):
    """A seeded timeline of frames and reports over the three strategies, an
    unguarded flow and a metered one: every status and every retry-after."""
    rng = np.random.default_rng(seed)
    svc, r = service(manual_clock), ref()
    t, shed = 1000, 0
    try:
        for _step in range(260):
            t += int(rng.choice([3, 17, 40, 90, 130, 700], p=[
                .3, .25, .2, .12, .1, .03]))
            at(manual_clock, t)
            n = int(rng.integers(1, 12))
            flows = rng.choice([1, 2, 3, 4, 5], size=n)
            if rng.random() < 0.45:
                # a spell of bad calls now and then: trips, bad probes
                bad = rng.random() < 0.35
                rt = np.where(rng.random(n) < (0.8 if bad else 0.1),
                              rng.integers(51, 900, n),
                              rng.integers(1, 51, n))
                exc = rng.random(n) < (0.8 if bad else 0.05)
                svc.report_outcomes(flows, rt, exc)
                r.report(t, flows, rt, exc)
            else:
                status, remaining, _wait = svc.request_batch_arrays(flows)
                want, retry = r.decide_frame(t, flows, [1] * n)
                assert status.tolist() == want, (seed, t, flows)
                is_shed = status == DEGRADED
                assert remaining[is_shed].tolist() == [
                    x for x, s in zip(retry, want) if s == DEGRADED]
                shed += int(is_shed.sum())
        assert shed > 20  # the timeline met open breakers
        assert {m.state for m in r.machines.values()} != {BR.CLOSED} or shed
        stats = svc.outcome_stats()
        assert stats["reported"] == r.reported
    finally:
        svc.close()


def test_a_steps_tally_is_counted_without_a_wait_for_the_device(
        manual_clock):
    """What an outcome step did to the breakers comes back as a few bytes
    the ingest never waits for: the next report's ingest counts the steps
    that have finished, a scrape (``outcome_stats``) the rest."""
    import jax

    svc = service(manual_clock)
    try:
        at(manual_clock, 1000)
        svc.report_outcomes([3] * 8, [5] * 8, [1] * 8)
        at(manual_clock, 1010)
        svc.request_batch_arrays(np.array([3]))  # trips
        at(manual_clock, 1010 + REC)
        svc.request_batch_arrays(np.array([3]))  # the probe
        before = server_metrics().arm_totals()
        svc.report_outcomes([3], [5], [0])  # closes it
        assert len(svc._outcome_tallies) == 1
        assert "breaker_to_closed_total" not in grew(before)
        jax.block_until_ready(svc._outcome_tallies[0])
        svc.report_outcomes([4], [5], [0])
        assert grew(before)["breaker_to_closed_total"] == 1
        assert len(svc._outcome_tallies) == 1  # the second report's own
        svc.outcome_stats()
        assert not svc._outcome_tallies
        assert grew(before) == {
            "breaker_to_closed_total": 1, "outcome_frames_total": 2,
            "outcome_steps_total": 2, "outcome_step_rows_total": 2}
    finally:
        svc.close()


# -- ROADMAP Reach A1 --------------------------------------------------------------
def test_a_report_is_stamped_after_its_step_compiled(manual_clock,
                                                     monkeypatch):
    """A1's cause. A report is written into the bucket of the clock read
    under the service lock; the outcome step was built and compiled after
    that read, on the first report. On the chip that compile takes seconds,
    so the report sat in a bucket older than the breakers' stat interval by
    the time the next request looked: OK where DEGRADED was due. Here the
    first call of the step at each rung costs 3 s of the clock, as a compile
    does, on a service nobody warmed."""
    from sentinel_tpu.engine import outcome

    build = outcome.outcome_step_donating

    def slow_to_compile(config, **kw):
        real, seen = build(config, **kw), set()

        def step(state, slots, *rest):
            if slots.shape not in seen:
                seen.add(slots.shape)
                manual_clock.advance(3000)
            return real(state, slots, *rest)
        return step

    monkeypatch.setattr(outcome, "outcome_step_donating", slow_to_compile)
    svc = service(manual_clock, warm=False)
    try:
        at(manual_clock, 1000)
        assert svc.request_batch_arrays(np.array([3]))[0].tolist() == [OK]
        assert svc.report_outcomes([3] * 8, [5] * 8, [True] * 8) == 8
        manual_clock.advance(20)  # the next request, 20 ms after the ingest
        status, remaining, _ = svc.request_batch_arrays(np.array([3]))
        assert status.tolist() == [DEGRADED] and remaining.tolist() == [REC]
    finally:
        svc.close()


@pytest.mark.parametrize("with_breakers", [True, False])
def test_warmup_compiles_the_outcome_step_where_breakers_are_loaded(
        manual_clock, with_breakers):
    """With degrade rules loaded, ``warmup()`` leaves no compile for a report
    of any rung, the first one included. Without, it compiles no outcome step
    (the flow cells' set-up stays what it was) and a rung's first report
    compiles it, outside the lock and before its clock is read."""
    svc = service(manual_clock, with_breakers=with_breakers)
    try:
        assert svc._outcome_rungs() == (64, 256)
        assert svc._outcome_warm == (
            {(64, True), (256, True)} if with_breakers else set())
        at(manual_clock, 1000)
        svc.request_batch_arrays(np.array([1, 2, 3]))
        before = server_metrics().compiles_total
        for k in (1, 64, 65, 256):
            at(manual_clock, 1000 + k)
            assert svc.report_outcomes([1 + k % 4] * k, [5] * k,
                                       [False] * k) == k
        svc.request_batch_arrays(np.array([1, 2, 3]))
        if with_breakers:
            assert server_metrics().compiles_total == before
        assert svc._outcome_warm == {(64, with_breakers), (256, with_breakers)}
        # more rows than a wire report holds (an in-process caller): the
        # next rung up, compiled at its first use
        assert svc.report_outcomes([2] * 257, [5] * 257, [False] * 257) == 257
        assert svc._outcome_warm == {(c, with_breakers)
                                     for c in (64, 256, 1024)}
        assert svc.outcome_stats()["reported"] == 1 + 64 + 65 + 256 + 257
    finally:
        svc.close()


# -- what the steps say they did ---------------------------------------------------
def grew(before: dict) -> dict:
    after = server_metrics().arm_totals()
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


def test_the_breaker_arm_and_the_outcome_step_say_what_they_did(manual_clock):
    from sentinel_tpu.trace import ring

    svc = service(manual_clock)
    ring.reset_for_tests()
    ring.arm()
    try:
        at(manual_clock, 1000)
        before = server_metrics().arm_totals()
        svc.request_batch_arrays(np.array([4, 4, 5]))  # no guarded row
        assert grew(before) == {"decide_dispatch_total": 1,
                                "decide_rows_total": 3}
        svc.report_outcomes([3] * 8, [5] * 8, [1] * 8, xid=11)
        svc.report_outcomes([2] * 8, [5] * 8, [1] * 8, xid=12)
        svc.report_outcomes([99], [5], [0], xid=14)  # unknown flow: no step
        at(manual_clock, 1010)
        svc.request_batch_arrays(np.array([3, 3, 2, 1, 4]))  # two trip
        at(manual_clock, 1010 + REC)
        svc.request_batch_arrays(np.array([3, 3, 3, 2, 2]))  # two probes
        svc.report_outcomes([3, 2, 3], [5, 5, 5], [0, 1, 1], xid=13)
        at(manual_clock, 1020 + REC)
        svc.request_batch_arrays(np.array([3, 2]))
        svc.outcome_stats()  # a scrape: the last steps' tallies are counted
        assert grew(before) == {
            "decide_dispatch_total": 4, "decide_rows_total": 15,
            "decide_breaker_live_total": 3,
            "decide_guarded_rows_total": 4 + 5 + 2,
            "decide_degraded_rows_total": 3 + 3 + 1,
            "breaker_to_open_total": 2, "breaker_probe_tickets_total": 2,
            "breaker_to_closed_total": 1, "breaker_reopened_total": 1,
            "outcome_steps_total": 3, "outcome_frames_total": 4,
            "outcome_step_rows_total": 19}
        # a span per report, door to step issued, joined by `shard`
        ev = ring.events(stages={ring.OUTCOME_IN, ring.OUTCOME})
        came = {e["shard"]: e for e in ev if e["stage"] == "outcome_in"}
        went = {e["shard"]: e for e in ev if e["stage"] == "outcome"}
        assert sorted(came) == sorted(went) and len(came) == 4
        assert [(came[k]["xid"], came[k]["aux"], went[k]["aux"])
                for k in sorted(came)] == [
                    (11, 8, 8), (12, 8, 8), (14, 1, 0), (13, 3, 3)]
        assert all(went[k]["t_ns"] >= came[k]["t_ns"] for k in came)
        stages = server_metrics().stage_snapshot()
        for phase in ("outcome_lock_wait_ms", "outcome_launch_ms",
                      "outcome_age_ms"):
            assert stages[phase]["count"] >= 4, phase
    finally:
        ring.reset_for_tests()
        svc.close()


def test_the_sharded_step_says_the_same_of_its_breaker_arm():
    """The breaker arm's counts are stitched over the mesh: the packed
    verdicts of the four-shard step carry what the one-device step's do."""
    import jax

    from sentinel_tpu.engine import build_rule_table, make_state
    from sentinel_tpu.engine.decide import (
        ARM_BREAKER, ARM_DEGRADED_ROWS, ARM_GUARDED_ROWS, ARM_LIVE,
        ARM_PROBES, ARM_TO_OPEN, decide_donating, pack_requests, unpack_arms)
    from sentinel_tpu.engine.outcome import outcome_step_donating
    from sentinel_tpu.parallel.sharding import (
        make_flow_mesh, make_sharded_decide, shard_rules, shard_state)
    import jax.numpy as jnp

    cfg = CFG._replace(batch_size=64)
    rules = [ClusterFlowRule(f, 1e9, ThresholdMode.GLOBAL, "a")
             for f in range(1, 41)]
    degrade = [DegradeRule(f, DegradeStrategy.ERROR_COUNT, 5,
                           recovery_timeout_ms=REC, namespace="a")
               for f in range(1, 41, 3)]
    table, index = build_rule_table(cfg, rules, degrade_rules=degrade)
    mesh = make_flow_mesh(jax.devices()[:4])
    one = decide_donating(cfg, grouped=True)
    four = make_sharded_decide(cfg, mesh, grouped=True, donate=True)
    ostep = outcome_step_donating(cfg)
    slots = sorted(index.slot_of[f] for f in (1, 1, 4, 7, 7, 7, 2, 3, 40))
    bad = jnp.asarray([index.slot_of[f] for f in (1, 7, 40) for _ in range(8)],
                      jnp.int32)
    said = []
    for step, state, tab in (
            (one, make_state(cfg), table),
            (four, shard_state(make_state(cfg), mesh),
             shard_rules(table, mesh))):
        state = ostep(state, bad, jnp.full(24, 5, jnp.int32),
                      jnp.ones(24, jnp.int32), jnp.ones(24, bool),
                      jnp.int32(1000), tab.br_strategy, tab.br_slow_rt_ms)
        out = []
        for now in (1010, 1010 + REC):
            state, packed = step(state, tab, pack_requests(cfg, slots,
                                                           now=now))
            out.append(unpack_arms(np.asarray(packed)))
        said.append(np.stack(out))
    assert (said[0] == said[1]).all()
    trip, probe = said[0]
    assert trip[ARM_LIVE] & ARM_BREAKER and probe[ARM_LIVE] & ARM_BREAKER
    # guarded rows: flows 1 (2), 4, 7 (3), 40; flows 1, 7 and 40 trip
    assert trip[[ARM_GUARDED_ROWS, ARM_DEGRADED_ROWS, ARM_PROBES,
                 ARM_TO_OPEN]].tolist() == [7, 6, 0, 3]
    assert probe[[ARM_GUARDED_ROWS, ARM_DEGRADED_ROWS, ARM_PROBES,
                  ARM_TO_OPEN]].tolist() == [7, 3, 3, 0]
