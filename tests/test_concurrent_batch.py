"""Cluster concurrency limiting on the served path (PR 41): the service's
batched entry against the plain reference
(``cellbench/families/concurrent_reference.py``) over random interleavings,
with invariant (e) held after every step; the ring's wrap-around, two
timeouts in one table, expiry with no traffic, AVG_LOCAL, rule reloads with
tokens live; the codec (rev 9), both doors moving one gauge with single and
batch frames, and the fuzzer's new cases. CPU, tiny sizes, seeded, a
passed-in clock."""

import os
import socket
import struct
import sys
import time

import numpy as np
import pytest

from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.concurrent import (
    EXPIRY_SLACK_MS, TICK_MS, ConcurrentFlowRule, ConcurrentPlane)
from sentinel_tpu.cluster.token_service import (
    DefaultTokenService, TokenService, TokenResult, concurrent_batch_entry,
    decide_concurrent_requests)
from sentinel_tpu.engine import EngineConfig, TokenStatus
from sentinel_tpu.engine import concurrent as CE
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.metrics.server import server_metrics

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cellbench.families import concurrent_reference as R  # noqa: E402

OK, BLOCKED, NO_RULE, FAIL = 0, 1, 3, 5
RELEASE_OK, ALREADY = 6, 7


def make_service(rules, max_tokens=64, buckets=(8, 32), max_flows=16):
    svc = DefaultTokenService(
        EngineConfig(max_flows=max_flows, max_namespaces=2,
                     batch_size=buckets[-1]),
        serve_buckets=buckets, concurrent_max_tokens=max_tokens)
    svc.load_concurrent_rules(rules)
    svc.close()  # the timer off: the tests tick by hand, on their own clock
    return svc


def held_is_the_sum_of_live_tokens(svc, ref=None):
    """Invariant (e) on the plane read back from the device."""
    snap = svc.concurrent_stats()
    total = {}
    for flow, count, _at in snap["tokens"].values():
        total[flow] = total.get(flow, 0) + count
    for flow, held in snap["held"].items():
        assert held == total.get(flow, 0), (flow, held, total)
        if snap["level"][flow] >= 0 and ref is not None:
            assert held <= snap["level"][flow]
    if ref is not None:
        ref.check()
        assert {f: h for f, h in snap["held"].items() if h} == {
            f: h for f, h in ref.held.items() if h}
        assert len(snap["tokens"]) == len(ref.tokens)
    return snap


class Pair:
    """The service and the reference driven together: the releases of a
    call first, then expiry, then the acquires (the step's order)."""

    def __init__(self, svc, ref, clock):
        self.svc, self.ref, self.clock = svc, ref, clock
        self.ref_id = {}  # the service's id -> the reference's
        self.issued = set()

    def now(self):
        return self.clock.now_ms() - 1_700_000_000_000

    def step(self, ids, counts, rel):
        ids = np.asarray(ids, np.int64)
        rel = np.asarray(rel, bool)
        counts = np.asarray(counts, np.int32)
        status, remaining, wait, tokens = self.svc.request_concurrent_batch(
            ids, counts, rel)
        want = np.zeros(len(ids), np.int8)
        want_rem = np.zeros(len(ids), np.int32)
        now = self.now()
        for i in np.flatnonzero(rel):
            want[i] = self.ref.release(self.ref_id.pop(int(ids[i]), 0))
        self.ref.expire(now)
        for i in np.flatnonzero(~rel):
            st, rm, tok = self.ref.acquire(now, int(ids[i]), int(counts[i]))
            want[i], want_rem[i] = st, rm
            if st == OK:
                # (a): non-zero, never issued before
                assert tokens[i] != 0 and int(tokens[i]) not in self.issued
                self.issued.add(int(tokens[i]))
                self.ref_id[int(tokens[i])] = tok
            else:
                assert tokens[i] == 0
        assert status.tolist() == want.tolist()
        assert remaining[~rel].tolist() == want_rem[~rel].tolist()
        assert not wait.any() and not tokens[rel].any()
        return status, tokens


RULES = [ConcurrentFlowRule(1, 3), ConcurrentFlowRule(2, 5),
         ConcurrentFlowRule(3, 40), ConcurrentFlowRule(4, 1),
         ConcurrentFlowRule(5, 0)]


@pytest.mark.parametrize("seed", range(6))
def test_random_interleavings_equal_the_reference_row_for_row(
        manual_clock, seed):
    """Acquires (one size a flow), releases, duplicate and stale ids, ids of
    slots the ring has reused, expiry in between: every status and
    ``remaining`` equal to the reference's, (e) after every step. The ring
    of 64 slots goes round several times."""
    rng = np.random.default_rng(seed)
    svc = make_service(RULES)
    ref = R.Reference({r.flow_id: r.concurrency_level for r in RULES}, 2000)
    pair = Pair(svc, ref, manual_clock)
    size = {1: 1, 2: 2, 3: 3, 4: 1, 5: 1, 9: 1}
    gone = []  # ids released or expired: stale from then on
    for step in range(40):
        live = list(pair.ref_id)
        n_acq = int(rng.integers(0, 14))
        flows = rng.choice([1, 2, 3, 4, 5, 9], n_acq)
        n_rel = int(rng.integers(0, min(len(live), 10) + 1))
        back = [live[i] for i in rng.permutation(len(live))[:n_rel]]
        stale = ([int(rng.choice(gone))] if gone else []) + [0, -3, 10**15]
        dup = back[:1]
        rel_ids = back + stale + dup
        order = rng.permutation(n_acq + len(rel_ids))
        ids = np.concatenate([flows, rel_ids]).astype(np.int64)[order]
        rel = np.concatenate([np.zeros(n_acq, bool),
                              np.ones(len(rel_ids), bool)])[order]
        counts = np.array([0 if r else size[int(i)]
                           for i, r in zip(ids, rel)], np.int32)
        before = set(pair.ref_id)
        pair.step(ids, counts, rel)
        gone += list(before - set(pair.ref_id))
        held_is_the_sum_of_live_tokens(svc, ref)
        if step % 7 == 6:  # let everything out expire, then go on
            manual_clock.advance(2001)
            gone += list(pair.ref_id)
            pair.ref_id.clear()
            svc.concurrent_tick()
            ref.expire(pair.now())
            held_is_the_sum_of_live_tokens(svc, ref)
        else:
            manual_clock.advance(int(rng.integers(0, 300)))
    assert svc.concurrent_stats()["cursor"][CE.CUR_GEN] >= 3  # wrapped


def test_fill_block_release_and_the_one_row_calls(manual_clock):
    svc = make_service(RULES)
    got = [svc.request_concurrent_token(1) for _ in range(4)]
    assert [r.status for r in got] == [TokenStatus.OK] * 3 + [
        TokenStatus.BLOCKED]
    assert [r.remaining for r in got] == [2, 1, 0, 0]
    assert len({r.token_id for r in got[:3]}) == 3 and got[3].token_id == 0
    assert svc.release_concurrent_token(got[0].token_id).status == (
        TokenStatus.RELEASE_OK)
    assert svc.release_concurrent_token(got[0].token_id).status == (
        TokenStatus.ALREADY_RELEASE)
    assert svc.request_concurrent_token(1).ok
    assert svc.request_concurrent_token(99).status == (
        TokenStatus.NO_RULE_EXISTS)
    assert svc.request_concurrent_token(1, 0).status == TokenStatus.FAIL
    assert svc.request_concurrent_token(5).status == TokenStatus.BLOCKED
    held_is_the_sum_of_live_tokens(svc)


def test_mixed_sizes_in_one_flow_never_over_admit(manual_clock):
    svc = make_service([ConcurrentFlowRule(3, 10)])
    status, remaining, _w, _t = svc.request_concurrent_batch(
        np.full(4, 3), np.array([8, 1, 1, 1]))
    assert status[0] == OK and remaining[0] == 2
    snap = held_is_the_sum_of_live_tokens(svc)
    assert snap["held"][3] <= 10 and int((status == OK).sum()) >= 2


def test_a_release_frees_room_for_the_acquires_of_its_dispatch(manual_clock):
    svc = make_service(RULES)
    _s, _r, _w, tokens = svc.request_concurrent_batch(np.full(3, 1))
    ids = np.array([1, 1, tokens[0], tokens[1]], np.int64)
    status, _r, _w, fresh = svc.request_concurrent_batch(
        ids, None, np.array([0, 0, 1, 1], bool))
    assert status.tolist() == [OK, OK, RELEASE_OK, RELEASE_OK]
    assert fresh[0] and fresh[1] and not fresh[2:].any()
    assert held_is_the_sum_of_live_tokens(svc)["held"][1] == 3


def test_two_timeouts_in_one_table_and_expiry_with_no_traffic(manual_clock):
    """Short-lived tokens behind long-lived ones are reclaimed by ticks
    alone, no earlier than their timeout; the long-lived stay."""
    rules = [ConcurrentFlowRule(1, 30, resource_timeout_ms=60_000),
             ConcurrentFlowRule(2, 5, resource_timeout_ms=100)]
    svc = make_service(rules)
    assert svc.request_concurrent_batch(np.full(20, 1))[0].tolist() == [OK] * 20
    _s, _r, _w, short = svc.request_concurrent_batch(np.full(6, 2))
    assert (short[:5] != 0).all() and short[5] == 0
    manual_clock.advance(99)
    assert svc.concurrent_tick() == 0  # not a millisecond early
    assert svc.request_concurrent_token(2).status == TokenStatus.BLOCKED
    manual_clock.advance(1)
    assert svc.concurrent_tick() == 5
    snap = held_is_the_sum_of_live_tokens(svc)
    assert snap["held"] == {1: 20, 2: 0}
    assert svc.release_concurrent_token(int(short[0])).status == (
        TokenStatus.ALREADY_RELEASE)
    assert svc.request_concurrent_batch(np.full(5, 2))[0].tolist() == [OK] * 5
    assert server_metrics().concurrent_totals()[
        "concurrent_tokens_live"] == 25


def test_the_scan_goes_round_a_large_ring_in_sixteen_ticks(manual_clock):
    """A ring of 2,048 slots is examined 128 at a time: a token past its
    time is reclaimed within ``EXPIRE_STEPS`` steps, wherever it lies and
    however many are due with it (a client that died holding many: more
    than ``EXPIRE_MAX`` in a block go in one block-wide scatter)."""
    svc = make_service([ConcurrentFlowRule(1, 1000, resource_timeout_ms=50),
                        ConcurrentFlowRule(2, 1000, resource_timeout_ms=50)],
                       max_tokens=2048, buckets=(8, 256), max_flows=4)
    cfg = svc._conc.config
    assert cfg.expire_block == 128 and cfg.table_len == 2048 + 256
    for _ in range(3):  # 600 slots in a row, every one of them live
        assert (svc.request_concurrent_batch(np.full(200, 1))[0] == OK).all()
    # ... and a stretch where a block holds fewer than EXPIRE_MAX
    mixed = np.where(np.arange(200) % 3 == 0, 2, 9)
    assert int((svc.request_concurrent_batch(mixed)[0] == OK).sum()) == 67
    manual_clock.advance(50)
    taken = [svc.concurrent_tick() for _ in range(CE.EXPIRE_STEPS)]
    assert sum(taken) == 667 and max(taken) == 128
    assert 0 < min(t for t in taken if t) < CE.EXPIRE_MAX
    assert set(held_is_the_sum_of_live_tokens(svc)["held"].values()) == {0}
    assert EXPIRY_SLACK_MS >= CE.EXPIRE_STEPS * TICK_MS


def test_a_full_ring_answers_fail_and_counts_it(manual_clock):
    svc = make_service([ConcurrentFlowRule(1, 1000)], max_tokens=32)
    before = server_metrics().concurrent_totals()
    first = svc.request_concurrent_batch(np.full(31, 1))
    assert (first[0] == OK).all()
    again = svc.request_concurrent_batch(np.full(8, 1))[0]
    # the block starts again at slot 0, the one slot id 0 left unused;
    # every other slot still holds a token
    assert again.tolist() == [OK] + [FAIL] * 7
    moved = server_metrics().concurrent_totals()
    assert (moved["concurrent_table_full_total"]
            - before["concurrent_table_full_total"]) == 7
    assert held_is_the_sum_of_live_tokens(svc)["held"][1] == 32
    svc.request_concurrent_batch(first[3], None, np.ones(31, bool))
    assert (svc.request_concurrent_batch(np.full(8, 1))[0] == OK).all()


def test_avg_local_scales_the_level_with_the_connected_clients(manual_clock):
    svc = make_service([
        ConcurrentFlowRule(2, 2, ThresholdMode.AVG_LOCAL, namespace="gw"),
        ConcurrentFlowRule(3, 2)])
    assert int((svc.request_concurrent_batch(np.full(7, 2))[0] == OK).sum()) == 2
    svc.connected_count_changed("gw", 3)
    status = svc.request_concurrent_batch(np.array([2] * 7 + [3] * 3))[0]
    assert int((status[:7] == OK).sum()) == 4  # 2 x 3 clients, 2 were out
    assert int((status[7:] == OK).sum()) == 2  # GLOBAL does not scale
    svc.connected_count_changed("gw", 1)  # the level falls under held
    assert svc.request_concurrent_token(2).status == TokenStatus.BLOCKED
    snap = svc.concurrent_stats()
    assert snap["held"][2] == 6 and snap["level"][2] == 2


def test_a_rule_reload_keeps_live_tokens(manual_clock):
    svc = make_service(RULES)
    _s, _r, _w, tokens = svc.request_concurrent_batch(np.array([1, 1, 2]))
    svc.load_concurrent_rules([ConcurrentFlowRule(2, 1),
                               ConcurrentFlowRule(7, 2)])
    assert svc.request_concurrent_token(1).status == (
        TokenStatus.NO_RULE_EXISTS)  # its rule went
    assert svc.request_concurrent_token(2).status == TokenStatus.BLOCKED
    assert svc.request_concurrent_token(7).ok
    snap = held_is_the_sum_of_live_tokens(svc)
    assert snap["held"][1] == 2 and snap["level"][1] == -1
    # ... and keeps draining by release and by expiry
    assert svc.release_concurrent_token(int(tokens[0])).ok
    manual_clock.advance(2000)
    assert svc.concurrent_tick() == 3
    assert set(held_is_the_sum_of_live_tokens(svc)["held"].values()) == {0}
    # a retired flow's slot is given back when the table needs it
    svc.load_concurrent_rules(
        [ConcurrentFlowRule(100 + k, 1) for k in range(16)])
    assert svc.request_concurrent_token(115).ok
    with pytest.raises(ValueError, match="capacity"):
        svc.load_concurrent_rules(
            [ConcurrentFlowRule(200 + k, 1) for k in range(17)])


def test_no_rule_loaded_allocates_and_compiles_nothing():
    svc = DefaultTokenService(
        EngineConfig(max_flows=8, max_namespaces=2, batch_size=8))
    svc.load_concurrent_rules([])
    assert svc._conc is None and svc._conc_timer is None
    status, _r, _w, tokens = svc.request_concurrent_batch(
        np.array([1, 5]), None, np.array([False, True]))
    assert status.tolist() == [NO_RULE, ALREADY] and not tokens.any()
    assert svc.concurrent_tick() == 0 and svc.concurrent_stats() == {}
    assert not hasattr(svc, "concurrency") and not hasattr(svc, "_expiry")


def test_the_timer_ticks_when_no_dispatch_does():
    svc = DefaultTokenService(
        EngineConfig(max_flows=8, max_namespaces=2, batch_size=8),
        serve_buckets=(8,), concurrent_max_tokens=16)
    svc.load_concurrent_rules(
        [ConcurrentFlowRule(1, 2, resource_timeout_ms=80)])
    try:
        assert svc._conc_timer.is_alive()
        assert svc.request_concurrent_batch(np.full(2, 1))[0].tolist() == [
            OK, OK]
        deadline = time.monotonic() + 5
        while svc.concurrent_stats()["held"][1] and time.monotonic() < deadline:
            time.sleep(0.02)
        assert svc.concurrent_stats()["held"][1] == 0  # no request came
    finally:
        svc.close()
    assert svc._conc_timer is None
    svc.reopen()
    assert svc._conc_timer.is_alive()
    svc.close()


def test_a_dispatch_past_the_largest_bucket_is_cut_releases_first(
        manual_clock):
    svc = make_service([ConcurrentFlowRule(1, 100)], max_tokens=256,
                       buckets=(8, 32))
    _s, _r, _w, tokens = svc.request_concurrent_batch(np.full(80, 1))
    assert (tokens != 0).all()
    ids = np.concatenate([np.full(90, 1), tokens[:70]])
    rel = np.concatenate([np.zeros(90, bool), np.ones(70, bool)])
    status, _r, _w, _t = svc.request_concurrent_batch(ids, None, rel)
    assert (status[90:] == RELEASE_OK).all()
    assert (status[:90] == OK).all()  # 10 were out, 90 more fit
    assert held_is_the_sum_of_live_tokens(svc)["held"][1] == 100


def test_levels_past_what_the_sums_hold_are_refused():
    with pytest.raises(ValueError, match="concurrency_level"):
        make_service([ConcurrentFlowRule(1, CE.MAX_LEVEL + 1)])
    with pytest.raises(ValueError, match="largest serve bucket"):
        ConcurrentPlane(8, 16, (8, 32))


def test_big_counts_and_big_levels_stay_exact(manual_clock):
    svc = make_service([ConcurrentFlowRule(1, CE.MAX_LEVEL),
                        ConcurrentFlowRule(2, 100_000)])
    status, remaining, _w, _t = svc.request_concurrent_batch(
        np.array([1] * 4 + [2] * 4),
        np.array([400_000, 400_000, 400_000, 2**30] + [30_000] * 4))
    assert status.tolist() == [OK, OK, BLOCKED, BLOCKED, OK, OK, OK, BLOCKED]
    assert remaining[:2].tolist() == [CE.MAX_LEVEL - 400_000,
                                      CE.MAX_LEVEL - 800_000]
    assert remaining[4:].tolist() == [70_000, 40_000, 10_000, 10_000]


# -- codec rev 9 -----------------------------------------------------------------
def test_the_codec_round_trips():
    assert P.WIRE_REV == 9 and {28, 29} <= P.KNOWN_TYPES
    ids = np.array([7, 2**40, -1], np.int64)
    frame = P.encode_batch_concurrent_acquire(9, ids, [1, 2, 3])
    (flen,) = struct.unpack_from(">H", frame)
    assert flen == len(frame) - 2 == 5 + 2 + 3 * 13
    assert P.peek_type(frame[2:]) == 28
    xid, i2, c2, p2 = P.decode_batch_concurrent_acquire(frame[2:])
    assert xid == 9 and (i2 == ids).all() and c2.tolist() == [1, 2, 3]
    rel = P.encode_batch_concurrent_release(-10, ids)
    assert P.peek_type(rel[2:]) == 29 and len(rel) == 2 + 7 + 24
    assert P.decode_batch_concurrent_release(rel[2:])[1].tolist() == (
        ids.tolist())
    rsp = P.encode_batch_concurrent_response(
        9, 28, [0, 1, 3], [4, 0, 0], [0, 0, 0], [2**41 + 5, 0, 0])
    assert len(rsp) == 2 + 7 + 3 * 17
    got = P.decode_batch_concurrent_response(rsp[2:])
    assert got[1].tolist() == [0, 1, 3] and got[4].tolist() == [
        2**41 + 5, 0, 0]
    rsp = P.encode_batch_concurrent_response(-10, 29, [6, 7, 7])
    assert len(rsp) == 2 + 7 + 3
    assert P.decode_batch_concurrent_response(rsp[2:])[1].tolist() == [6, 7, 7]
    with pytest.raises(ValueError):
        P.encode_batch_concurrent_release(1, np.zeros(8192, np.int64))
    # an acquire frame holds what its reply frame can answer, not what its
    # own narrower rows would
    assert P.MAX_ACQUIRE_PER_FRAME == 3854 < P.MAX_BATCH_PER_FRAME == 5040
    full = P.encode_batch_concurrent_acquire(
        1, np.ones(P.MAX_ACQUIRE_PER_FRAME, np.int64))
    assert len(P.decode_batch_concurrent_acquire(full[2:])[1]) == 3854
    assert len(P.encode_batch_concurrent_response(
        1, 28, np.zeros(P.MAX_ACQUIRE_PER_FRAME, np.int8))) == 2 + 65525
    with pytest.raises(ValueError):
        P.encode_batch_concurrent_acquire(1, np.ones(3855, np.int64))
    # the single frames keep their bytes
    one = P.encode_response(P.FlowResponse(
        3, P.MsgType.CONCURRENT_ACQUIRE, 0, 2, 0, 77))
    assert len(one) == 2 + 5 + 9 + 8
    assert P.decode_response(one[2:]).token_id == 77


@pytest.mark.parametrize("what", ["runt", "short", "long"])
@pytest.mark.parametrize("mtype", [28, 29])
def test_a_malformed_frame_is_refused_by_the_codec(what, mtype):
    good = (P.encode_batch_concurrent_acquire(1, [1, 2]) if mtype == 28
            else P.encode_batch_concurrent_release(1, [1, 2]))[2:]
    bad = {"runt": good[:6], "short": good[:-1], "long": good + b"\0"}[what]
    decode = (P.decode_batch_concurrent_acquire if mtype == 28
              else P.decode_batch_concurrent_release)
    with pytest.raises(ValueError):
        decode(bad)


def test_any_spi_implementation_serves_the_batch_frames():
    class Mine(TokenService):
        def request_concurrent_token(self, flow_id, acquire=1,
                                     prioritized=False):
            return TokenResult(TokenStatus.OK, 5, 0, flow_id * 10 + acquire)

        def release_concurrent_token(self, token_id):
            return TokenResult(TokenStatus.ALREADY_RELEASE)

    out = concurrent_batch_entry(Mine())(
        np.array([1, 2, 3]), np.array([1, 2, 0]), np.array([0, 0, 1], bool))
    assert out[0].tolist() == [0, 0, 7] and out[3].tolist() == [11, 22, 0]
    reqs = [P.FlowRequest(1, 4, 1, False, P.MsgType.CONCURRENT_ACQUIRE),
            P.FlowRequest(2, 41, 0, False, P.MsgType.CONCURRENT_RELEASE)]
    assert decide_concurrent_requests(Mine(), reqs, [False, True], 5) == [
        (0, 5, 0, 41), (7, 0, 0, 0)]


# -- the doors --------------------------------------------------------------------
def _served(kind):
    svc = DefaultTokenService(
        EngineConfig(max_flows=16, max_namespaces=2, batch_size=32),
        serve_buckets=(8, 32), fuse_depths=(), concurrent_max_tokens=8192)
    svc.load_concurrent_rules(RULES)
    if kind == "native":
        from sentinel_tpu.cluster.server_native import NativeTokenServer

        server = NativeTokenServer(svc, host="127.0.0.1", port=0,
                                   max_batch=32)
    else:
        from sentinel_tpu.cluster.server import TokenServer

        server = TokenServer(svc, port=0, batch_window_ms=0.5)
    server.start()
    return svc, server


@pytest.fixture(scope="module")
def native_door():
    """One native server for the module's door tests (each start compiles
    the service's steps): every test leaves its flows empty."""
    svc, server = _served("native")
    yield svc, server
    server.stop()
    svc.close()


@pytest.fixture(scope="module")
def asyncio_door():
    svc, server = _served("asyncio")
    yield svc, server
    server.stop()
    svc.close()


@pytest.mark.parametrize("kind", ["native", "asyncio"])
def test_single_and_batch_frames_on_one_socket_move_one_gauge(kind, request):
    svc, server = request.getfixturevalue(kind + "_door")
    client = TokenClient("127.0.0.1", server.port, timeout_ms=5000)
    try:
        one = client.request_concurrent_token(2)  # type 3
        assert one.ok and one.token_id and one.remaining == 4
        status, remaining, _w, tokens = client.request_concurrent_batch(
            [2] * 6 + [99], 1)  # type 28
        assert status.tolist() == [OK] * 4 + [BLOCKED] * 2 + [NO_RULE]
        assert remaining[:4].tolist() == [3, 2, 1, 0]
        assert len(set(tokens[:4].tolist()) | {one.token_id}) == 5
        assert not tokens[4:].any()
        # a batch release of the single frame's id and one of the batch's
        back = client.release_concurrent_batch(
            [one.token_id, tokens[0], tokens[0], 0])  # type 29
        assert back.tolist() == [RELEASE_OK, RELEASE_OK, ALREADY, ALREADY]
        # a single release of a batch frame's id
        assert client.release_concurrent_token(int(tokens[1])).status == (
            TokenStatus.RELEASE_OK)  # type 4
        assert client.release_concurrent_token(int(tokens[1])).status == (
            TokenStatus.ALREADY_RELEASE)
        assert svc.concurrent_stats()["held"][2] == 2
        assert client.request_concurrent_batch([], 1)[0].size == 0
        assert client.release_concurrent_batch([]).size == 0
        # pipelined on one connection: the release frame, then the acquire
        raw = (P.encode_batch_concurrent_release(900, tokens[2:4])
               + P.encode_batch_concurrent_acquire(901, [2] * 5))
        sock = socket.create_connection(("127.0.0.1", server.port))
        try:
            sock.sendall(raw)
            reader, frames = P.FrameReader(), []
            sock.settimeout(5)
            while len(frames) < 2:
                frames += reader.feed(sock.recv(4096))
        finally:
            sock.close()
        by_xid = {P.peek_xid(f): P.decode_batch_concurrent_response(f)
                  for f in frames}
        assert by_xid[900][1].tolist() == [RELEASE_OK] * 2
        assert by_xid[901][1].tolist() == [OK] * 5
        assert client.release_concurrent_batch(by_xid[901][4]).tolist() == [
            RELEASE_OK] * 5
    finally:
        client.close()
    assert held_is_the_sum_of_live_tokens(svc)["held"][2] == 0


def test_the_native_door_closes_a_malformed_frame_and_answers_an_empty_one(
        native_door):
    _svc, server = native_door
    for mtype, row in ((28, 13), (29, 8)):
        sock = socket.create_connection(("127.0.0.1", server.port))
        sock.settimeout(5)
        sock.sendall(struct.pack(">HibH", 7, 5, mtype, 0))  # empty
        assert sock.recv(64) == struct.pack(">HibH", 7, 5, mtype, 0)
        body = struct.pack(">ibH", 6, mtype, 2) + b"\0" * (2 * row + 1)
        sock.sendall(struct.pack(">H", len(body)) + body)  # over-long
        assert sock.recv(64) == b""  # closed
        sock.close()


def _acquire_frame_of(xid, flow, rows):
    """A type-28 frame of ``rows`` whole rows, past the encoder's bound too."""
    raw = bytearray(P.encode_batch_request(xid, np.full(rows, flow, np.int64)))
    raw[6] = 28
    return bytes(raw)


@pytest.mark.parametrize("rows", [3855, 5040])
def test_the_codec_refuses_an_acquire_frame_its_reply_cannot_answer(rows):
    with pytest.raises(ValueError, match="3854"):
        P.decode_batch_concurrent_acquire(_acquire_frame_of(1, 3, rows)[2:])


@pytest.mark.parametrize("rows", [3854, 3855, 5040])
@pytest.mark.parametrize("kind", ["native", "asyncio"])
def test_an_acquire_frame_holds_the_rows_its_reply_can_answer(
        kind, rows, request):
    """3,854 rows are answered in one frame whose u16 length is whole
    (7 + 3,854 x 17 = 65,525); 3,855 rows, and the 5,040 that the request's
    13-byte rows would allow, close the connection and move no gauge: a
    reply of their rows would wrap the length and misframe every reply
    behind it, with the tokens just issued leaking until expiry."""
    svc, server = request.getfixturevalue(kind + "_door")
    sock = socket.create_connection(("127.0.0.1", server.port))
    sock.settimeout(30)
    try:
        sock.sendall(_acquire_frame_of(77, 3, rows))
        buf = b""
        while len(buf) < 2 or len(buf) < 2 + struct.unpack(">H", buf[:2])[0]:
            chunk = sock.recv(1 << 16)
            if not chunk:
                break
            buf += chunk
    finally:
        sock.close()
    if rows > P.MAX_ACQUIRE_PER_FRAME:
        assert buf == b""  # closed, nothing answered
        assert held_is_the_sum_of_live_tokens(svc)["held"][3] == 0
        tokens = np.zeros(0, np.int64)
    else:
        assert len(buf) == 2 + 7 + rows * 17
        xid, status, remaining, _w, tokens = (
            P.decode_batch_concurrent_response(buf[2:]))
        assert xid == 77 and len(status) == rows
        assert status.tolist() == [OK] * 40 + [BLOCKED] * (rows - 40)
        assert remaining[:40].tolist() == list(range(39, -1, -1))
        assert len(set(tokens[:40].tolist())) == 40 and not tokens[40:].any()
    # the door serves on, and the client's chunks stay inside the bound
    client = TokenClient("127.0.0.1", server.port, timeout_ms=30000)
    try:
        if len(tokens):
            assert client.release_concurrent_batch(tokens[:40]).tolist() == [
                RELEASE_OK] * 40
        status, _r, _w, again = client.request_concurrent_batch(
            np.full(rows, 3))
        assert status.tolist() == [OK] * 40 + [BLOCKED] * (rows - 40)
        assert client.release_concurrent_batch(again[:40]).tolist() == [
            RELEASE_OK] * 40
    finally:
        client.close()
    assert held_is_the_sum_of_live_tokens(svc)["held"][3] == 0


def test_the_lane_never_mixes_kinds_and_counts_its_dispatches(native_door):
    from sentinel_tpu.trace import ring as flight

    svc, server = native_door
    client = TokenClient("127.0.0.1", server.port, timeout_ms=5000)
    before = server_metrics().concurrent_totals()
    flight.arm(sample=0.0)
    since = time.monotonic_ns()
    try:
        _s, _r, _w, tokens = client.request_concurrent_batch([3] * 10, 3)
        assert client.release_concurrent_batch(tokens).tolist() == [
            RELEASE_OK] * 10
        # (the reply lane answers first, counts after, and stamps DEVICE_OUT
        # on its own: wait for both)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            events = flight.events(since_ns=since)
            if (server_metrics().concurrent_totals()[
                    "concurrent_dispatch_total"]
                    >= before["concurrent_dispatch_total"] + 2
                    and sum(e["stage"] == "device_out" for e in events) >= 2):
                break
            time.sleep(0.01)
    finally:
        flight.disarm()
        client.close()
    moved = {k: v - before[k]
             for k, v in server_metrics().concurrent_totals().items()}
    assert moved["concurrent_dispatch_total"] == 2
    assert moved["concurrent_acquire_rows_total"] == 10
    assert moved["concurrent_release_rows_total"] == 10
    assert moved["concurrent_blocked_total"] == 0
    lane = [e for e in events if e["stage"] == "device_in"
            and e["shard"] == flight.CONCURRENT_LANE]
    assert [e["aux"] for e in lane] == [10, 10]
    out = [e for e in events if e["stage"] == "device_out"]
    assert [e["shard"] for e in out] == [flight.CONCURRENT_LANE] * 2
    text = server_metrics().render()
    assert "sentinel_server_concurrent_acquire_rows_total" in text
    assert "sentinel_server_concurrent_tokens_live" in text
    assert "concurrent_tokens_live" in server_metrics().stage_snapshot()


def test_the_fuzzers_cases_leave_the_door_serving():
    """The corpus with the rev-9 frames in it (valid, mutated, truncated,
    over-long, dripped, acquire frames at and past the reply's row bound)
    against the bare door: the oracle's four round
    trips, types 28 and 29 among them, keep passing. (Against the server,
    with concurrency rules loaded: ``tests/test_native_server.py``'s runs
    of the same corpus.)"""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native"))
    import fuzz_frontdoor

    out = fuzz_frontdoor.run_fuzz_raw(iters=40, seed=41, oracle_every=8)
    assert out["oracle_checks"] == 7
