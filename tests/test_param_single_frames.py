"""Single PARAM_FLOW frames (type 2, the reference client's one-request
frame) on the native TCP door's data plane (PR 45): verdict for verdict
against the benchmark's plain reference and against the same rows in one
BATCH_PARAM_FLOW frame; frames of several value counts interleaved on one
connection and across connections; single and batch frames of one value
count in one pull, each answered in its own layout; the malformed frame,
the frame with no value, STANDBY, age shed, a full queue and brownout; the
counters. The reference client's single CONCURRENT_ACQUIRE / _RELEASE frames
(types 3 and 4) took the same way and are held to their layouts here. CPU,
tiny geometry, seeded."""

import socket
import struct
import threading
import time

import numpy as np
import pytest

from cellbench.families.hotparam_reference import Reference
from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster.server_native import (
    NativeTokenServer,
    native_available,
)
from sentinel_tpu.cluster.token_service import (
    ClusterParamFlowRule,
    DefaultTokenService,
)
from sentinel_tpu.core import clock as clock_mod
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.engine import EngineConfig, TokenStatus
from sentinel_tpu.engine.param import ParamConfig
from sentinel_tpu.metrics.server import ServerMetrics, server_metrics
from sentinel_tpu.overload import (
    AdmissionController,
    BrownoutLevel,
    OverloadConfig,
)

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native library not built")

OK, BLOCKED, NO_RULE = (int(TokenStatus.OK), int(TokenStatus.BLOCKED),
                        int(TokenStatus.NO_RULE_EXISTS))
STANDBY, OVERLOAD = int(TokenStatus.STANDBY), int(TokenStatus.OVERLOAD)
PARAM_FLOW, BATCH_PARAM_FLOW = 2, 27
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=256)
PCFG = ParamConfig(max_param_rules=8, depth=4, width=512)
BUCKETS = (64, 256)
# rule -> (count, {value: item threshold})
RULES = {1: (5.0, {11: 10.0, 77: 7.0}), 2: (3.0, {77: 2.0}), 3: (4.0, {})}
VALUES = [11, 77, 5, 6, 7, 8, 9, 10]


def make_service():
    svc = DefaultTokenService(CFG, param_config=PCFG, serve_buckets=BUCKETS,
                              fuse_depths=())
    svc.load_param_rules([
        ClusterParamFlowRule(r, count, item_thresholds=tuple(items.items())
                             or None, namespace=f"ns{r % 2}")
        for r, (count, items) in RULES.items()])
    return svc


@pytest.fixture(scope="module")
def clock():
    mc = ManualClock()
    prev = clock_mod.set_clock(mc)
    yield mc
    clock_mod.set_clock(prev)


@pytest.fixture(scope="module")
def svc(clock):
    service = make_service()
    yield service
    service.close()


@pytest.fixture(scope="module")
def server(svc):
    srv = NativeTokenServer(svc, port=0, max_batch=256, idle_ttl_s=None)
    srv.start()
    yield srv
    srv.stop()


@pytest.fixture(autouse=True)
def fresh_window(clock):
    clock.advance(2_000)  # whatever an earlier test counted has slid out


def single(xid, rule, acquire, values, msg_type=P.MsgType.PARAM_FLOW):
    return P.encode_request(P.FlowRequest(
        xid, rule, acquire, False, msg_type, tuple(values)))


def seeded(n, k, seed):
    """``n`` requests ``(rule, acquire, [k values])``; rule 9 has no rule."""
    rng = np.random.default_rng(seed)
    rules = rng.choice([1, 2, 3, 9], n, p=[0.4, 0.3, 0.2, 0.1])
    return [(int(r), int(rng.integers(1, 3)),
             [int(v) for v in rng.choice(VALUES, k, replace=False)])
            for r in rules]


def collect(sock, n_frames, timeout=10.0):
    """``n_frames`` response payloads off ``sock``; ``None`` when the server
    closed the connection first."""
    reader, frames = P.FrameReader(), []
    sock.settimeout(timeout)
    deadline = time.monotonic() + timeout
    while len(frames) < n_frames and time.monotonic() < deadline:
        try:
            data = sock.recv(1 << 16)
        except socket.timeout:
            break
        if not data:
            return None
        frames += reader.feed(data)
    return frames


def exchange(port, payloads, n_frames, timeout=10.0):
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as s:
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        for p in payloads:
            s.sendall(p)
        return collect(s, n_frames, timeout)


def settled(read, want, timeout=5.0):
    """The reply lane answers first and counts after, and the door's IO
    thread counts a frame after ``send()`` returned: wait on the counter."""
    deadline = time.monotonic() + timeout
    while read() < want and time.monotonic() < deadline:
        time.sleep(0.01)
    return read()


def single_totals():
    return server_metrics().param_single_totals()


# -- verdicts --------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 2, 4])
def test_single_frames_agree_with_the_reference_and_the_batch_frame(
        server, svc, clock, k):
    requests = seeded(120, k, 40 + k)
    t = svc._engine_now()
    want = Reference(RULES, 500, 2).decide_all(
        t, *zip(*requests))
    assert {OK, BLOCKED, NO_RULE} <= set(want)
    blob = b"".join(single(500 + i, *r) for i, r in enumerate(requests))
    frames = exchange(server.port, [blob], len(requests))
    assert frames is not None and len(frames) == len(requests)
    replies = [P.decode_response(f) for f in frames]
    assert {r.msg_type for r in replies} == {P.MsgType.PARAM_FLOW}
    by_xid = {r.xid: r for r in replies}
    assert sorted(by_xid) == list(range(500, 500 + len(requests)))
    assert [by_xid[500 + i].status for i in range(len(requests))] == want
    assert not any(r.remaining or r.wait_ms for r in replies)
    # the same rows as ONE batch frame, one window later
    clock.advance(2_000)
    batch = P.encode_batch_param_request(
        7, [r for r, _a, _v in requests], [a for _r, a, _v in requests],
        [v for _r, _a, v in requests])
    (reply,) = exchange(server.port, [batch], 1)
    assert P.peek_type(reply) == BATCH_PARAM_FLOW
    assert P.decode_batch_response(reply)[1].tolist() == want


def test_value_counts_interleaved_on_and_across_connections(server):
    """Frames of 1, 2 and 4 values interleaved on three connections at once:
    every frame is answered once, under type 2, with its own xid, on its
    own connection (fresh values on a rule: each passes; rule 9: none)."""
    n_conn, per_conn = 3, 60
    before, ctl0 = server.stats(), single_totals()
    results = [None] * n_conn

    def one(ci):
        base = 10_000 * (ci + 1)
        blob = b""
        for j in range(per_conn):
            k = (1, 2, 4)[(j + ci) % 3]
            rule = 9 if j % 10 == 9 else 3
            values = [base + 8 * j + v for v in range(k)]
            blob += single(base + j, rule, 1, values)
        results[ci] = exchange(server.port, [blob], per_conn)

    threads = [threading.Thread(target=one, args=(ci,))
               for ci in range(n_conn)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    for ci, frames in enumerate(results):
        assert frames is not None and len(frames) == per_conn
        replies = [P.decode_response(f) for f in frames]
        assert all(len(f) == 14 for f in frames)  # FLOW's reply layout
        assert {r.msg_type for r in replies} == {P.MsgType.PARAM_FLOW}
        base = 10_000 * (ci + 1)
        assert sorted(r.xid for r in replies) == list(
            range(base, base + per_conn))
        for r in replies:
            assert r.status == (NO_RULE if (r.xid - base) % 10 == 9 else OK)
    after = server.stats()
    assert (after["param_single_frames_in"]
            - before["param_single_frames_in"]) == n_conn * per_conn
    assert (single_totals()["param_control_frames_total"]
            == ctl0["param_control_frames_total"])


# -- the door alone: what a pull holds, what a reply looks like ------------------
@pytest.fixture()
def bare_door():
    from sentinel_tpu.native.lib import Frontdoor

    door = Frontdoor("127.0.0.1", 0)
    yield door
    door.stop()


def staging(rows=512):
    return dict(
        ids=np.empty(rows, np.int64), counts=np.empty(rows, np.int32),
        prios=np.empty(rows, np.uint8), hashes=np.empty(8192, np.int64),
        f_fd=np.empty(rows, np.int32), f_gen=np.empty(rows, np.int32),
        f_xid=np.empty(rows, np.int32), f_n=np.empty(rows, np.int32),
        f_type=np.empty(rows, np.uint8), f_rx_ns=np.empty(rows, np.int64),
        wake_ns=np.zeros(1, np.int64))


def pull(door, block, want_frames):
    """Pulls until ``want_frames`` frames came: ``[(n, k, nv, columns)]``."""
    got, deadline = [], time.monotonic() + 5
    while sum(p[1] for p in got) < want_frames and time.monotonic() < deadline:
        r = door.wait_any_into(block, timeout_ms=100, max_n=512)
        if r is None:
            continue
        n, k, nv = r
        got.append((n, k, nv, {
            name: block[name][:k].copy()
            for name in ("f_fd", "f_gen", "f_xid", "f_n", "f_type",
                         "f_rx_ns")
        } | {"ids": block["ids"][:n].copy(),
             "counts": block["counts"][:n].copy(),
             "hashes": block["hashes"][:n * max(nv, 0)].copy()}))
    return got


def arrived(door, frames_in):
    return settled(lambda: door.stats()["frames_in"], frames_in) == frames_in


def test_single_and_batch_frames_of_one_k_share_a_pull(bare_door):
    """The arena's rule: a param pull is a run of frames with one ``k``. A
    single frame is a one-row frame of the arena, so it rides between the
    batch frames around it, and each is answered in its own layout."""
    door = bare_door
    batch = P.encode_batch_param_request(
        21, [1, 2, 3], [1, 1, 2], [[101, 102], [103, 104], [105, 106]])
    blob = (single(20, 1, 1, [11, 12]) + batch + single(22, 3, 4, [13, 14])
            + single(23, 2, 1, [15]))  # another k: the next pull
    with socket.create_connection(("127.0.0.1", door.port)) as sock:
        sock.sendall(blob)
        assert arrived(door, 4)
        block = staging()
        first, second = pull(door, block, 4)
        n, k, nv, cols = first
        assert (n, k, nv) == (5, 3, 2)
        assert cols["f_type"].tolist() == [PARAM_FLOW, BATCH_PARAM_FLOW,
                                           PARAM_FLOW]
        assert cols["f_n"].tolist() == [1, 3, 1]
        assert cols["f_xid"].tolist() == [20, 21, 22]
        assert cols["ids"].tolist() == [1, 1, 2, 3, 3]
        assert cols["counts"].tolist() == [1, 1, 1, 2, 4]
        assert cols["hashes"].reshape(5, 2).tolist() == [
            [11, 12], [101, 102], [103, 104], [105, 106], [13, 14]]
        assert (cols["f_rx_ns"] > 0).all()  # stamped like any data frame
        assert second[:3] == (1, 1, 1)
        assert second[3]["hashes"].tolist() == [15]
        status = np.array([1, 0, 1, 3, 0], np.int8)
        frames = tuple(cols[c] for c in ("f_fd", "f_gen", "f_xid", "f_n",
                                         "f_type", "f_rx_ns"))
        door.submit(frames, status, np.arange(5, dtype=np.int32),
                    np.zeros(5, np.int32))
        replies = collect(sock, 3)
    by_xid = {P.peek_xid(f): f for f in replies}
    a, b = P.decode_response(by_xid[20]), P.decode_response(by_xid[22])
    assert (a.msg_type, a.status, a.remaining) == (P.MsgType.PARAM_FLOW, 1, 0)
    assert (b.msg_type, b.status, b.remaining) == (P.MsgType.PARAM_FLOW, 0, 4)
    assert len(by_xid[20]) == len(by_xid[22]) == 14
    assert P.peek_type(by_xid[21]) == BATCH_PARAM_FLOW
    _xid, st, rem, _wait = P.decode_batch_response(by_xid[21])
    assert (st.tolist(), rem.tolist()) == ([0, 1, 3], [1, 2, 3])
    spans = door.span_stats()
    assert settled(lambda: door.span_stats()["door_residence_ms"][0], 3) == 3
    assert spans["door_in_ms"][0] == 4  # the three span histograms count them


def test_single_concurrency_frames_ride_the_concurrency_arena(bare_door):
    """Types 3 and 4 have FLOW's fixed layout: a one-row frame each in the
    concurrency arena, in arrival order with the batch frames; an acquire's
    reply carries its token id behind FLOW's reply, a release's is FLOW's."""
    door = bare_door
    blob = (single(30, 7, 2, (), P.MsgType.CONCURRENT_ACQUIRE)
            + P.encode_batch_concurrent_release(31, [555, 556])
            + single(32, 777, 0, (), P.MsgType.CONCURRENT_RELEASE)
            + single(33, 8, 1, (), P.MsgType.FLOW))
    with socket.create_connection(("127.0.0.1", door.port)) as sock:
        sock.sendall(blob)
        assert arrived(door, 4)
        block = staging()
        pulls = pull(door, block, 4)
        conc = next(p for p in pulls if p[2] == -1)
        flow = next(p for p in pulls if p[2] == 0)
        cols = conc[3]
        assert cols["f_type"].tolist() == [3, 29, 4]
        assert cols["f_n"].tolist() == [1, 2, 1]
        assert cols["ids"].tolist() == [7, 555, 556, 777]
        assert cols["counts"][0] == 2
        assert flow[3]["f_type"].tolist() == [1]
        frames = tuple(cols[c] for c in ("f_fd", "f_gen", "f_xid", "f_n",
                                         "f_type", "f_rx_ns"))
        door.submit(frames, np.array([0, 0, 1, 0], np.int8),
                    np.array([3, 0, 0, 0], np.int32), np.zeros(4, np.int32),
                    np.array([(9 << 32) | 5, 0, 0, 0], np.int64))
        replies = {P.peek_xid(f): f for f in collect(sock, 3)}
    acq = P.decode_response(replies[30])
    assert (acq.msg_type, acq.status, acq.remaining, acq.token_id) == (
        P.MsgType.CONCURRENT_ACQUIRE, 0, 3, (9 << 32) | 5)
    assert len(replies[30]) == 22 and len(replies[32]) == 14
    rel = P.decode_response(replies[32])
    assert (rel.msg_type, rel.status) == (P.MsgType.CONCURRENT_RELEASE, 0)
    assert P.decode_batch_concurrent_response(replies[31])[1].tolist() == [
        0, 1]


# -- malformed, and the frame with no value --------------------------------------
def short_frames():
    whole = single(40, 3, 1, [1, 2, 3])[2:]
    return {
        # the body ends inside its third value
        "two_of_three_values": whole[:-5],
        # FLOW's body and nothing behind it: not even the count of values
        "no_count_byte": whole[:5 + 13],
    }


@pytest.mark.parametrize("which", sorted(short_frames()))
def test_a_frame_shorter_than_its_values_closes_its_connection_alone(
        server, which):
    body = short_frames()[which]
    bad = struct.pack(">H", len(body)) + body
    with socket.create_connection(("127.0.0.1", server.port)) as other:
        other.sendall(single(41, 3, 1, [4001]))
        assert len(collect(other, 1)) == 1
        assert exchange(server.port, [bad], 1, timeout=3.0) is None
        # the neighbour is served on
        other.sendall(single(42, 3, 1, [4002]))
        (frame,) = collect(other, 1)
        reply = P.decode_response(frame)
        assert (reply.xid, reply.msg_type, reply.status) == (
            42, P.MsgType.PARAM_FLOW, OK)


def test_a_frame_with_no_value_is_answered_as_before(server):
    """No row of the sketch: it goes to the control plane, whose answer (OK,
    nothing remaining, no wait: ``request_params_token`` with no values) is
    what it was."""
    door0, ctl0 = server.stats(), single_totals()
    frames = exchange(server.port, [single(50, 3, 1, [])
                                    + single(51, 3, 1, [5001])], 2)
    by_xid = {r.xid: r for r in map(P.decode_response, frames)}
    for xid in (50, 51):
        r = by_xid[xid]
        assert (r.msg_type, r.status, r.remaining, r.wait_ms) == (
            P.MsgType.PARAM_FLOW, OK, 0, 0)
    door1 = server.stats()
    assert (door1["param_single_frames_in"]
            - door0["param_single_frames_in"]) == 1
    grew = settled(lambda: single_totals()["param_control_frames_total"]
                   - ctl0["param_control_frames_total"], 1)
    assert grew == 1


# -- the lanes' own answers ------------------------------------------------------
class _Pinned(AdmissionController):
    """A brownout level held by hand: the wiring, not the estimator."""

    def __init__(self, lvl):
        super().__init__(config=OverloadConfig(), metrics=ServerMetrics())
        self._forced = lvl

    def level(self, now=None):
        return self._forced


def _answers(kw):
    service = make_service()
    srv = NativeTokenServer(service, port=0, max_batch=256, idle_ttl_s=None,
                            **kw)
    srv.start()
    try:
        blob = (single(60, 3, 1, [6001]) + single(61, 3, 1, [6002, 6003])
                + single(62, 3, 1, (), P.MsgType.FLOW))
        frames = exchange(srv.port, [blob], 3)
    finally:
        srv.stop()
        service.close()
    assert frames is not None and len(frames) == 3
    return {r.xid: r for r in map(P.decode_response, frames)}


@pytest.mark.parametrize("case", ["standby", "overload", "brownout"])
def test_the_lanes_answer_a_single_param_frame_as_a_single_flow_frame(
        clock, case):
    kw, status = {
        "standby": ({"standby_of": "primary"}, STANDBY),
        # every pull is older than no time at all: shed by age
        "overload": ({"shed_age_ms": 0.0}, OVERLOAD),
        # the ladder's floor sheds what it does not pass locally
        "brownout": ({"overload": _Pinned(BrownoutLevel.DEGRADE)}, None),
    }[case]
    got = _answers(kw)
    assert got[60].msg_type == got[61].msg_type == P.MsgType.PARAM_FLOW
    assert got[62].msg_type == P.MsgType.FLOW
    flow = got[62]
    for xid in (60, 61):
        if status is not None:
            assert got[xid].status == status
            assert got[xid].wait_ms == flow.wait_ms
        else:  # a local pass or OVERLOAD, as the ladder answers a FLOW frame
            assert got[xid].status in (OK, OVERLOAD)
    if status is not None:
        assert flow.status == status


class _Stalled(DefaultTokenService):
    """A service whose param dispatch waits for the test: the device lane
    stands still in it and the intake lane's queue fills behind it."""

    gate = threading.Event()

    def dispatch_params_batch(self, *args, **kwargs):
        self.gate.wait(10)
        return super().dispatch_params_batch(*args, **kwargs)


def test_a_full_queue_refuses_a_single_param_frame_as_a_single_flow_frame(
        clock):
    """The device lane held in a dispatch, pulls of one frame each behind
    it: the intake lane's put gives up after ``shed_age_ms`` and answers
    the pull itself (``queue_full``), a single PARAM_FLOW frame by FLOW's
    reply under type 2 with the retry hint, as it answers a FLOW frame."""
    service = _Stalled(CFG, param_config=PCFG, serve_buckets=BUCKETS,
                       fuse_depths=())
    service.load_param_rules([ClusterParamFlowRule(3, 4.0, namespace="ns1")])
    srv = NativeTokenServer(service, port=0, max_batch=256, idle_ttl_s=None,
                            shed_age_ms=50.0)
    srv.start()
    shed0 = server_metrics().shed_totals().get("queue_full", 0)
    n = 10
    _Stalled.gate.clear()
    try:
        with socket.create_connection(("127.0.0.1", srv.port),
                                      timeout=10) as s:
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            for i in range(n):  # a pull each: the last one a FLOW frame
                s.sendall(single(80 + i, 3, 1, [8000 + i]) if i < n - 1
                          else single(80 + i, 3, 1, (), P.MsgType.FLOW))
                time.sleep(0.03)
            shed = settled(lambda: server_metrics().shed_totals().get(
                "queue_full", 0) - shed0, 1)
            _Stalled.gate.set()
            frames = collect(s, n)
    finally:
        _Stalled.gate.set()
        srv.stop()
        service.close()
    assert shed >= 1
    assert frames is not None and len(frames) == n
    got = {r.xid: r for r in map(P.decode_response, frames)}
    assert sorted(got) == list(range(80, 80 + n))  # each answered once
    hint = srv.overload.retry_hint_ms
    for xid, r in got.items():
        assert r.msg_type == (P.MsgType.FLOW if xid == 80 + n - 1
                              else P.MsgType.PARAM_FLOW)
        assert r.status in (OK, OVERLOAD)
        if r.status == OVERLOAD:  # shed by the full queue, or by age behind it
            assert r.wait_ms == hint
    assert got[80].status == OK  # the dispatch that stood still was decided
    refused = [r for r in got.values() if r.status == OVERLOAD]
    assert any(r.msg_type == P.MsgType.PARAM_FLOW for r in refused)


@pytest.mark.parametrize("max_batch, dispatches", [(256, 2), (1, 3)])
def test_a_trickle_of_tiny_pulls_is_one_dispatch_past_the_fuse_depth(
        clock, max_batch, dispatches):
    """The fusion depth is a budget of host prep in pulls of up to
    ``max_batch`` rows: seven pulls of one frame each, queued behind a
    dispatch that stands still, are together far less than one such pull
    and go out in ONE dispatch, not in two of ``fuse_depth`` = 4 and 3.
    Where a pull of one row is a full pull (``max_batch`` 1) the depth
    holds as it did: the first, then 4, then 3."""
    service = _Stalled(CFG, param_config=PCFG, serve_buckets=BUCKETS,
                       fuse_depths=())
    service.load_param_rules([ClusterParamFlowRule(3, 1e6, namespace="ns1")])
    srv = NativeTokenServer(service, port=0, max_batch=max_batch,
                            idle_ttl_s=None, shed_age_ms=None)
    assert srv.fuse_depth == 4
    srv.start()
    before = single_totals()
    grew = lambda: {k: v - before[k]  # noqa: E731
                    for k, v in single_totals().items()}
    n = 8
    _Stalled.gate.clear()
    try:
        with socket.create_connection(("127.0.0.1", srv.port),
                                      timeout=10) as s:
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            for i in range(n):  # a pull each; the first one's dispatch waits
                s.sendall(single(90 + i, 3, 1, [9000 + i]))
                time.sleep(0.03)
            _Stalled.gate.set()
            frames = collect(s, n)
        assert frames is not None and len(frames) == n
        assert settled(lambda: grew()["param_single_frames_total"], n) == n
    finally:
        _Stalled.gate.set()
        srv.stop()
        service.close()
    g = grew()
    assert g["param_single_pulls_total"] == n
    # the first, then the rest (as far as the depth lets them join)
    assert g["param_single_dispatch_total"] == dispatches
    assert all(P.decode_response(f).status == OK for f in frames)


# -- counters --------------------------------------------------------------------
def test_the_counters_count_single_frames_a_pull_and_a_dispatch(server):
    n = 90
    before, door0 = single_totals(), server.stats()
    spans0 = server_metrics().stage_snapshot()["door_residence_ms"]["count"]
    blob = b"".join(single(700 + i, 3, 1, [7000 + i]) for i in range(n))
    frames = exchange(server.port, [blob], n)
    assert len(frames) == n
    grew = lambda: {k: v - before[k]  # noqa: E731
                    for k, v in single_totals().items()}
    assert settled(lambda: grew()["param_single_frames_total"], n) == n
    g = grew()
    assert g["param_single_rows_total"] == n  # one value a frame
    assert 0 < g["param_single_dispatch_total"] <= g[
        "param_single_pulls_total"] <= n
    assert g["param_control_frames_total"] == 0
    assert (server.stats()["param_single_frames_in"]
            - door0["param_single_frames_in"]) == n
    snap = server_metrics().stage_snapshot()
    assert all(name in snap for name in before)
    assert settled(lambda: server_metrics().stage_snapshot()[
        "door_residence_ms"]["count"] - spans0, n) == n
    text = server_metrics().render()
    assert "sentinel_server_param_single_frames_total" in text
    assert "sentinel_server_param_control_frames_total" in text


# -- what the deployment's serve buckets reach ----------------------------------
@pytest.mark.parametrize("rows", [64, 256, 1024])
def test_the_whole_step_kernel_refuses_the_deployments_sketch_unasked(rows):
    """``demo-cluster-param-1k``'s largest serve bucket (1,024) is inside
    the whole-step Pallas kernel's row cap, so ``impl: auto`` would probe
    that kernel at the 512 MiB sketch, whose one plane is the kernel's whole
    VMEM limit: refused by arithmetic, at trace time, before Mosaic is
    asked (on the chip the compiler did not come back with its refusal)."""
    import functools

    import jax
    import jax.numpy as jnp

    from sentinel_tpu.ops.cms_pallas import (
        cms_decide_update_pallas,
        vmem_bytes,
    )

    P_, B, D, W = 1024, 2, 4, 16384  # hot-param-1k's sketch
    assert vmem_bytes(rows, P_, D, W) > 64 << 20
    assert vmem_bytes(1024, 256, 2, 2048) < 64 << 20  # the default still fits
    shapes = (
        jax.ShapeDtypeStruct((B * D, P_, W), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.int32),
        jax.ShapeDtypeStruct((rows, D), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.float32),
        jax.ShapeDtypeStruct((rows,), jnp.bool_),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    with pytest.raises(ValueError, match="MiB of VMEM"):
        jax.eval_shape(functools.partial(
            cms_decide_update_pallas, P=P_, B=B, D=D, W=W, bucket_ms=500),
            *shapes)
