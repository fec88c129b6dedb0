"""Phases inside one dispatch (PR 24): the always-on phase histograms, the
same boundaries in the flight recorder, names for what the device runs, the
program's own compile counter, one clock for the operator's profile.
"""

import glob
import json
import logging
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.server import TokenServer
from sentinel_tpu.cluster.server_native import (
    NativeTokenServer,
    native_available,
)
from sentinel_tpu.cluster.token_service import (
    ClusterParamFlowRule,
    DefaultTokenService,
    Materializer,
    halves,
)
from sentinel_tpu.core.log import record_log
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig, pack_requests
from sentinel_tpu.engine import make_state
from sentinel_tpu.engine.param import ParamConfig
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.metrics.profiler import ProfilerHook
from sentinel_tpu.metrics.server import server_metrics
from sentinel_tpu.parallel import make_flow_mesh
from sentinel_tpu.trace import ring, spans

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import decide_golden  # noqa: E402

G = ThresholdMode.GLOBAL
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=64)
CAP = CFG.batch_size
SM = server_metrics()

SERVICE_PHASES = ("prep_ms", "lock_wait_ms", "launch_ms", "device_wait_ms",
                  "fetch_ms", "account_ms")
DISPATCH_SIDE = ("permit_wait_ms", "prep_ms", "lock_wait_ms", "launch_ms")
READ_SIDE = ("device_wait_ms", "fetch_ms")
DECIDE_SIDE = READ_SIDE + ("account_ms",)
LANE_DISPATCHES = 200
# what lies between the stamps of a stage's phases, per dispatch: 0.015 ms
# (decide_ms) to 0.037 (dispatch_ms) on an idle sandbox, where a tenth of a
# 40-row dispatch's 0.15 / 0.37 ms is no more than that
GLUE_MS = 0.08
ARMED_DISPATCHES = 50


def _service(**kw):
    svc = DefaultTokenService(CFG, **kw)
    svc.load_rules([ClusterFlowRule(flow_id=i, count=50.0, mode=G)
                    for i in range(1, 9)])
    return svc


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(1, 10, n).astype(np.int64)


def _counts():
    return {k: v["count"] for k, v in SM.stage_snapshot().items()
            if isinstance(v, dict) and "count" in v}


def _sums():
    return {k: v["sum"] for k, v in SM.snapshot()["stages"].items()}


def _settled(read, want, timeout=10.0):
    """The one bounded wait of a reader that follows a native-lane reply:
    the reply lane answers first and counts after, so poll ``read()`` until
    it gives ``want``. Returns the last reading either way."""
    deadline = time.monotonic() + timeout
    while (got := read()) != want and time.monotonic() < deadline:
        time.sleep(0.002)
    return got


def _accounted():
    return SM.account_ms.snapshot()["count"]


# -- (a) one record per phase per dispatch ------------------------------------
# path -> (service arguments, row counts of the calls, device dispatches)
PATHS = {
    "single": ({}, (10,), 1),
    "oversized": ({"fuse_depths": ()}, (3 * CAP + 5,), 4),
    "fused": ({}, (6 * CAP,), 2),  # scan(4) + scan(2)
    "mesh": ({"mesh": 4}, (10, 2 * CAP), 2),  # a sharded step, a sharded scan
}


@pytest.fixture(scope="module")
def per_path():
    """Each path driven once: ``{path: growth of every histogram's count}``."""
    grew = {}
    for path, (kw, calls, _n) in PATHS.items():
        kw = dict(kw)
        if "mesh" in kw:
            kw["mesh"] = make_flow_mesh(jax.devices()[:kw["mesh"]])
        svc = _service(**kw)
        before = _counts()
        for rows in calls:
            out = svc.request_batch_arrays(_ids(rows))
            assert out[0].shape == (rows,)
        after = _counts()
        grew[path] = {k: after[k] - before[k] for k in after}
        svc.close()
    return grew


@pytest.mark.parametrize("phase", SERVICE_PHASES)
@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_service_phase_records_once_per_dispatch(per_path, path, phase):
    assert per_path[path][phase] == PATHS[path][2]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_lane_phases_stay_silent_without_the_native_lane(per_path, path):
    assert per_path[path]["permit_wait_ms"] == 0
    assert per_path[path]["reply_queue_wait_ms"] == 0


# -- the native lane: counts, reconciliation, flight-recorder chains ----------
@pytest.fixture(scope="module")
def lane_run():
    if not native_available():
        pytest.skip("native library not built")
    ring.reset_for_tests()
    svc = _service()
    server = NativeTokenServer(svc, port=0, idle_ttl_s=None)
    server.start()
    client = TokenClient("127.0.0.1", server.port, timeout_ms=5000)
    run = {}
    try:
        def drive(n):
            # the lane counts a dispatch after its reply: wait for each, so
            # no account half runs beside the next dispatch and slows the
            # lane's glue between the stamps (one GIL)
            base = _accounted()
            for i in range(n):
                out = client.request_batch_arrays(_ids(40, seed=i))
                assert out is not None
                assert _settled(_accounted, base + i + 1) == base + i + 1

        drive(1)  # settle
        c0, s0, f0 = _counts(), _sums(), SM.reply_first_total
        drive(LANE_DISPATCHES)  # disarmed: what the histograms cost alone
        c1, s1 = _counts(), _sums()
        run["counts"] = {k: c1[k] - c0[k] for k in c1}
        run["counts"]["reply_first_total"] = SM.reply_first_total - f0
        run["sums"] = {k: s1[k] - s0[k] for k in s1}
        run["events_disarmed"] = ring.events()
        ring.arm(sample=1.0)
        drive(ARMED_DISPATCHES)
        run["phases"] = spans.dispatch_phases()
        run["events"] = ring.events()
        run["threads"] = {e["thread"] for e in ring.events()}
    finally:
        client.close()
        server.stop()
        svc.close()
        ring.reset_for_tests()
    return run


@pytest.mark.parametrize("phase", DISPATCH_SIDE + ("reply_queue_wait_ms",)
                         + DECIDE_SIDE)
def test_every_phase_records_once_per_lane_dispatch(lane_run, phase):
    # one request at a time, so one lane dispatch per request
    assert lane_run["counts"]["dispatch_ms"] == LANE_DISPATCHES
    assert lane_run["counts"]["decide_ms"] == LANE_DISPATCHES
    assert lane_run["counts"][phase] == LANE_DISPATCHES


@pytest.fixture(scope="module")
def whole_run():
    """Off the lane: the asyncio door calls the materializer whole, inside
    the ``decide_ms`` it starts before the dispatch."""
    svc = _service()
    server = TokenServer(svc, port=0, batch_window_ms=0.0)
    server.start()
    client = TokenClient("127.0.0.1", server.port, timeout_ms=5000)
    try:
        assert client.request_batch_arrays(_ids(40)) is not None  # settle
        s0, f0 = _sums(), SM.reply_first_total
        for i in range(LANE_DISPATCHES):
            assert client.request_batch_arrays(_ids(40, seed=i)) is not None
        s1 = _sums()
    finally:
        client.close()
        server.stop()
        svc.close()
    return {"sums": {k: s1[k] - s0[k] for k in s1},
            "reply_first": SM.reply_first_total - f0}


# on the lane decide_ms is the read half (the accounting follows the reply);
# a door that calls the materializer whole holds all three, and the dispatch
@pytest.mark.parametrize("run,whole,parts", [
    ("lane", "dispatch_ms", DISPATCH_SIDE),
    ("lane", "decide_ms", READ_SIDE),
    ("whole", "decide_ms", DISPATCH_SIDE[1:] + DECIDE_SIDE),
])
def test_the_phases_reconcile_with_the_stage_they_split(request, run, whole,
                                                        parts):
    sums = request.getfixturevalue(run + "_run")["sums"]
    total = sums[whole]
    split = sum(sums[p] for p in parts)
    assert total > 0
    # a tenth of the whole, or GLUE_MS a dispatch where that is more: the
    # stamps' own glue, a handful of bytecodes each, does not shrink with
    # a 40-row dispatch, and under a loaded machine a GIL hand-over lands
    # between two of them
    assert abs(split - total) <= max(0.10 * total,
                                     GLUE_MS * LANE_DISPATCHES), (
        whole, total, {p: sums[p] for p in parts})


def test_the_lane_counts_every_dispatch_after_its_reply(lane_run, whole_run):
    assert lane_run["counts"]["reply_first_total"] == LANE_DISPATCHES
    assert whole_run["reply_first"] == 0  # counted before the door's write


def test_device_out_follows_reply_out_and_account_ms_is_the_accounting_alone(
        lane_run):
    """In time order on each reply lane: a dispatch's ``fetched``, its
    frame's ``reply_out``, its ``account`` and its ``device_out``."""
    by_lane = {}
    for e in lane_run["events"]:
        if e["stage"] in ("fetched", "reply_out", "account", "device_out"):
            by_lane.setdefault(e["thread"], []).append(e["stage"])
    assert sum(map(len, by_lane.values())) == 4 * ARMED_DISPATCHES
    for stages in by_lane.values():
        assert stages == ["fetched", "reply_out", "account",
                          "device_out"] * (len(stages) // 4)


def test_armed_rings_yield_complete_chains_joined_across_threads(lane_run):
    chains = [d for d in lane_run["phases"] if d["complete"]]
    assert len(chains) >= ARMED_DISPATCHES - 1  # the newest may be in flight
    seqs = [d["seq"] for d in chains]
    assert len(set(seqs)) == len(seqs)
    assert sorted(seqs) == list(range(min(seqs), max(seqs) + 1))
    for d in chains:
        assert d["rows"] == 40
        # dispatched by the device lane, materialized by a reply lane
        assert d["dispatchThread"] != d["replyThread"]
        for key in ("permitWaitMs", "prepMs", "lockWaitMs", "launchMs",
                    "waitMs", "fetchMs", "accountMs"):
            assert d[key] is not None and d[key] >= 0, (key, d)
    assert len(lane_run["threads"]) >= 3  # intake, device lane, reply lane


def test_disarmed_rings_record_nothing(lane_run):
    assert lane_run["events_disarmed"] == []


# -- answer first, count after (PR 35) ----------------------------------------
SLOW_S = 0.2  # what the slowed account half sleeps
PCFG = ParamConfig(max_param_rules=8, depth=4, width=512)


def _slow_account(monkeypatch, seconds=SLOW_S):
    real = DefaultTokenService._account

    def slow(self, *a, **kw):
        time.sleep(seconds)
        return real(self, *a, **kw)

    monkeypatch.setattr(DefaultTokenService, "_account", slow)


def _counted():
    """Everything one flow dispatch counts, by what counts it."""
    arms = SM.arm_totals()
    return {"account_ms": _accounted(),
            "dispatches": arms["decide_dispatch_total"],
            "rows": arms["decide_rows_total"],
            "verdicts": sum(v["count"] for v in SM.snapshot()["verdicts"]),
            "reads": SM.verdict_host_reads_total,
            "reply_first": SM.reply_first_total}


def _grew(before):
    after = _counted()
    return {k: after[k] - before[k] for k in after}


@pytest.fixture
def lane():
    if not native_available():
        pytest.skip("native library not built")
    svc = _service()
    server = NativeTokenServer(svc, port=0, idle_ttl_s=None)
    server.start()
    client = TokenClient("127.0.0.1", server.port, timeout_ms=5000)
    base = _accounted()
    assert client.request_batch_arrays(_ids(40)) is not None  # compiled
    assert _settled(_accounted, base + 1) == base + 1
    yield server, client
    client.close()
    server.stop()  # a second stop() is a no-op
    svc.close()


def test_a_reply_does_not_wait_for_a_slow_account_half(lane, monkeypatch):
    _server, client = lane
    _slow_account(monkeypatch)
    took = []
    for i in range(3):
        before = _counted()
        t0 = time.perf_counter()
        assert client.request_batch_arrays(_ids(40, seed=i)) is not None
        took.append(time.perf_counter() - t0)
        # the reply is here and its dispatch is not counted yet ...
        assert _grew(before)["account_ms"] == 0
        # ... and after a bounded settle it is, whole
        assert _settled(_accounted, before["account_ms"] + 1) == (
            before["account_ms"] + 1)
        assert _grew(before) == {"account_ms": 1, "dispatches": 1, "rows": 40,
                                 "verdicts": 40, "reads": 1, "reply_first": 1}
    assert min(took) < SLOW_S / 4, took


@pytest.mark.parametrize("entry", ["request_batch_arrays",
                                   "request_params_batch"])
def test_a_synchronous_caller_has_counted_before_it_returns(entry,
                                                            monkeypatch):
    _slow_account(monkeypatch, 0.02)  # were it counted later, it would show
    svc = DefaultTokenService(CFG, param_config=PCFG)
    svc.load_rules([ClusterFlowRule(flow_id=1, count=50.0, mode=G)])
    svc.load_param_rules([ClusterParamFlowRule(1, 5.0)])
    try:
        before = _counted()
        if entry == "request_batch_arrays":
            out = svc.request_batch_arrays(np.ones(10, np.int64))
            want = {"account_ms": 1, "dispatches": 1, "rows": 10,
                    "verdicts": 10, "reads": 1, "reply_first": 0}
        else:
            out = svc.request_params_batch(
                np.ones(10, np.int64), np.ones(10, np.int32),
                np.arange(10, dtype=np.int64).reshape(10, 1))
            # no flow dispatch: the arm counters stand still
            want = {"account_ms": 1, "dispatches": 0, "rows": 0,
                    "verdicts": 10, "reads": 1, "reply_first": 0}
        assert out[0].shape == (10,)
        assert _grew(before) == want
    finally:
        svc.close()


def test_a_materializer_is_one_body_in_two_halves():
    svc = _service()
    try:
        before = _counted()
        mat = svc.dispatch_batch_arrays(_ids(10))
        assert isinstance(mat, Materializer)
        read, account = halves(mat)
        status, remaining, wait = read()
        assert status.shape == remaining.shape == wait.shape == (10,)
        assert _grew(before)["account_ms"] == 0  # read, not yet counted
        account()
        account()  # at most once
        assert _grew(before) == {"account_ms": 1, "dispatches": 1, "rows": 10,
                                 "verdicts": 10, "reads": 1, "reply_first": 0}
        # a plain callable (a foreign service's, a wrapper's) is read whole
        plain = lambda: "verdicts"  # noqa: E731
        assert halves(plain) == (plain, None)
        # the composite of an oversized burst follows from its parts
        before = _counted()
        whole = svc.dispatch_batch_arrays(_ids(6 * CAP))  # scan(4) + scan(2)
        assert whole.read()[0].shape == (6 * CAP,)
        assert _grew(before)["account_ms"] == 0
        whole.account(True)
        assert _grew(before) == {
            "account_ms": 2, "dispatches": 2, "rows": 6 * CAP,
            "verdicts": 6 * CAP, "reads": 2, "reply_first": 2}
    finally:
        svc.close()


def test_a_read_that_raises_is_never_accounted(monkeypatch):
    svc = _service()
    try:
        before = _counted()
        mat = svc.dispatch_batch_arrays(_ids(10))
        monkeypatch.setattr(
            DefaultTokenService, "_read_verdicts",
            staticmethod(lambda packed: 1 / 0))
        with pytest.raises(ZeroDivisionError):
            mat()
        mat.account()
        assert _grew(before)["account_ms"] == 0
    finally:
        svc.close()


def test_the_permit_is_free_once_the_read_half_is_done():
    if not native_available():
        pytest.skip("native library not built")
    svc = _service()
    server = NativeTokenServer(svc, port=0, idle_ttl_s=None,
                               max_device_inflight=1)
    ids = _ids(10)
    args = (ids, np.ones(10, np.int32), np.zeros(10, bool))
    try:
        before = _counted()
        mat, release, _ = server._tracked_dispatch(
            svc.dispatch_batch_arrays, *args)
        assert server._device_inflight == 1
        mat.read()
        # the permit bounds device work in flight, not counters
        assert server._device_inflight == 0
        assert _grew(before)["account_ms"] == 0
        mat.account(True)
        release()  # idempotent with the read half's own release
        assert server._device_inflight == 0
        assert _grew(before)["account_ms"] == 1

        def boom():
            raise RuntimeError("device fell over")

        mat, _release, _ = server._tracked_dispatch(lambda *a: boom, *args)
        with pytest.raises(RuntimeError):
            mat.read()
        assert server._device_inflight == 0  # on the exception path too
        mat.account(True)  # what the lane does next: nothing to count
        assert _grew(before)["account_ms"] == 1
    finally:
        svc.close()


def test_a_submit_that_raises_is_still_accounted_once(lane, monkeypatch):
    server, client = lane
    (door,) = server._doors
    real, calls = door.submit_many, []

    def flaky(*a):
        calls.append(1)
        if len(calls) == 1:
            raise OSError("connection reset")
        return real(*a)

    monkeypatch.setattr(door, "submit_many", flaky, raising=False)
    before = _counted()
    lost = TokenClient("127.0.0.1", server.port, timeout_ms=300)
    try:
        assert lost.request_batch_arrays(_ids(40)) is None  # never answered
    finally:
        lost.close()
    assert client.request_batch_arrays(_ids(40, seed=1)) is not None
    assert _settled(_accounted, before["account_ms"] + 2) == (
        before["account_ms"] + 2)
    assert _grew(before) == {"account_ms": 2, "dispatches": 2, "rows": 80,
                             "verdicts": 80, "reads": 2, "reply_first": 2}


def test_stop_leaves_every_materialized_dispatch_counted_once(
        lane, monkeypatch):
    server, client = lane
    _slow_account(monkeypatch, 0.05)
    before = _counted()
    n = 6
    for i in range(n):  # each reply is here before its dispatch is counted
        assert client.request_batch_arrays(_ids(40, seed=i)) is not None
    assert _grew(before)["account_ms"] < n
    server.stop()  # joins the reply lanes: no wait needed after it
    want = {"account_ms": n, "dispatches": n, "rows": 40 * n,
            "verdicts": 40 * n, "reads": n, "reply_first": n}
    assert _grew(before) == want
    time.sleep(0.1)
    assert _grew(before) == want  # and nothing counts twice later


def test_the_trace_command_serves_the_phases():
    import sentinel_tpu.transport.handlers  # noqa: F401  (registers commands)
    from sentinel_tpu.transport.command import get_command

    ring.reset_for_tests()
    svc = _service()
    try:
        ring.arm(sample=0.0)  # aggregate events record at any sample
        svc.request_batch_arrays(_ids(10))
        out = get_command("cluster/server/trace")({"action": "phases"}, "")
        (d,) = out["dispatches"]
        assert d["complete"] and d["rows"] == 10
        # no native lane: no permit, so prep's start is unknown
        assert d["permitWaitMs"] is None and d["prepMs"] is None
        json.dumps(out)
    finally:
        svc.close()
        ring.reset_for_tests()


# -- (d) the compile counter ---------------------------------------------------
def test_compiles_after_warmup_count_a_step_forced_lazily():
    seen = []

    class Keep(logging.Handler):
        def emit(self, record):
            seen.append(record.getMessage())

    handler = Keep(level=logging.WARNING)
    record_log.addHandler(handler)
    ring.reset_for_tests()
    svc = _service()
    try:
        total0 = SM.compiles_total
        after0 = SM.compiles_after_warmup_total
        svc.warmup()
        assert SM.compiles_total > total0
        assert SM.compile_ms.snapshot()["count"] >= SM.compiles_total - total0
        svc.request_batch_arrays(_ids(10))
        svc.request_batch_arrays(_ids(10), np.arange(10, dtype=np.int32) % 3)
        svc.request_batch_arrays(_ids(2 * CAP))  # fused, uniform: warmed
        assert SM.compiles_after_warmup_total == after0
        assert not seen
        # the one variant warmup() leaves cold on one chip: a fused span of
        # mixed acquires (PERF.md section 7)
        ring.arm(sample=0.0)
        svc.request_batch_arrays(
            _ids(2 * CAP), np.arange(2 * CAP, dtype=np.int32) % 3 + 1)
        assert SM.compiles_after_warmup_total == after0 + 1
        assert len(seen) == 1 and "decide_fused_d2_b64_mixed" in seen[0]
        (ev,) = ring.events(stages={ring.COMPILE})
        assert ev["aux"] >= 0
        stages = SM.stage_snapshot()
        assert stages["compiles_total"] == SM.compiles_total
        assert "sentinel_server_compiles_after_warmup_total " in SM.render()
    finally:
        record_log.removeHandler(handler)
        svc.close()
        ring.reset_for_tests()


# -- (c) names for what the device runs ---------------------------------------
def _lowered(kind):
    from sentinel_tpu.engine.decide import (
        decide_donating,
        decide_fused_donating,
    )
    from sentinel_tpu.engine.outcome import outcome_step_donating
    from sentinel_tpu.parallel import (
        make_sharded_decide,
        shard_rules,
        shard_state,
    )

    cfg, table, _index = decide_golden._setup()
    batch = pack_requests(cfg, [0, 1, 2], now=1000)
    stacked = np.stack([batch] * 2, axis=1)
    now = jnp.int32(1000)
    if kind == "single":
        step = decide_donating(cfg, grouped=True, uniform=False)
        return step.__name__, step.lower(make_state(cfg), table, batch)
    if kind == "fused":
        step = decide_fused_donating(cfg, 2, grouped=True, uniform=True)
        return step.__name__, step.lower(make_state(cfg), table, stacked)
    if kind == "outcome":
        step = outcome_step_donating(cfg)
        z = jnp.zeros(8, jnp.int32)
        return step.__name__, step.lower(make_state(cfg), z, z, z,
                                         jnp.zeros(8, bool), now)
    mesh = make_flow_mesh(jax.devices()[:4])
    state, table = shard_state(make_state(cfg), mesh), shard_rules(table, mesh)
    if kind == "sharded":
        step = make_sharded_decide(cfg, mesh, grouped=True, uniform=True,
                                   donate=True)
        return step.__name__, step.jitted(table).lower(state, table, batch)
    step = make_sharded_decide(cfg, mesh, grouped=True, uniform=False,
                               donate=True, depth=2)
    return step.__name__, step.jitted(table).lower(state, table, stacked)


STEP_NAMES = {
    "single": "decide_b64_mixed",
    "fused": "decide_fused_d2_b64_uniform",
    "sharded": "decide_sharded_b64_uniform",
    "sharded_fused": "decide_sharded_fused_d2_b64_mixed",
    "outcome": "outcome_step",
}


@pytest.fixture(scope="module")
def lowered():
    return {kind: _lowered(kind) for kind in STEP_NAMES}


@pytest.mark.parametrize("kind", sorted(STEP_NAMES))
def test_the_jitted_step_carries_its_name_into_the_program(lowered, kind):
    name, low = lowered[kind]
    assert name == STEP_NAMES[kind]
    assert f"@jit_{name}" in low.as_text()


ARMS = ("roll_guard", "threshold", "shaping", "admit", "pacing",
        "occupy", "commit", "verdicts", "window_roll")


@pytest.mark.parametrize("kind", ("single", "sharded_fused"))
@pytest.mark.parametrize("arm", ARMS)
def test_every_arm_of_the_step_is_a_named_scope(lowered, kind, arm):
    # "jit(..)/commit/scatter-add" at top level, "commit/scatter-add" inside
    # a scan body
    assert re.search(rf'[/"]{arm}/', lowered[kind][1].as_text(debug_info=True))


def test_the_breaker_arm_is_a_named_scope_once_degrade_rules_load():
    from sentinel_tpu.engine import build_rule_table
    from sentinel_tpu.engine.decide import decide_donating
    from sentinel_tpu.engine.rules import DegradeRule

    cfg = decide_golden._setup()[0]
    table, _index = build_rule_table(
        cfg, [ClusterFlowRule(flow_id=1, count=5.0, mode=G)],
        degrade_rules=[DegradeRule(flow_id=1)])
    low = decide_donating(cfg, grouped=True, uniform=True).lower(
        make_state(cfg), table, pack_requests(cfg, [0], now=1000))
    assert re.search(r'[/"]breaker/', low.as_text(debug_info=True))


def test_the_psum_stitch_is_a_named_scope_of_the_sharded_step(lowered):
    text = lowered["sharded"][1].as_text(debug_info=True)
    assert "roll_guard/psum_stitch" in text
    assert "verdicts/psum_stitch" in text
    assert "psum_stitch" not in lowered["single"][1].as_text(debug_info=True)


# -- metadata only: verdicts bit-equal with the tree before the names ---------
@pytest.fixture(scope="module")
def stream_verdicts():
    return decide_golden.verdicts()


GOLDEN = np.load(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "data", "decide_golden.npz"))


@pytest.mark.parametrize("case", sorted(GOLDEN.files))
def test_verdicts_are_bit_equal_with_the_parent_tree(stream_verdicts, case):
    want = GOLDEN[case]
    assert set(np.unique(want[..., 0, :])) >= {0, 1, 2, 3, 4}  # every verdict
    np.testing.assert_array_equal(stream_verdicts[case], want)


# -- (e) one clock for the operator's profile ---------------------------------
def test_a_profile_and_its_span_artifact_share_one_clock(tmp_path):
    from jax.profiler import ProfileData

    ring.reset_for_tests()
    svc = _service()
    hook = ProfilerHook()
    try:
        svc.request_batch_arrays(_ids(10))
        assert hook.start(str(tmp_path))["profiling"] is True
        svc.request_batch_arrays(_ids(10))
        out = hook.stop()
    finally:
        svc.close()
        ring.reset_for_tests()
    with open(out["spans"], encoding="utf-8") as f:
        doc = json.load(f)
    sync_ns = doc["sync"]["monotonicNs"]
    assert doc["sync"]["annotation"] == "sentinel.sync"
    (d,) = doc["dispatches"]  # the dispatch inside the profiled window
    assert d["complete"] and d["startNs"] > sync_ns
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    found = [
        (e.start_ns, {k: v for k, v in e.stats})
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name == "sentinel.sync"
    ]
    assert len(found) == 1
    assert int(found[0][1]["t_ns"]) == sync_ns
