"""The batched hot-parameter lane (PR 27): ``request_params_batch`` against a
plain reference verdict for verdict, the one-row case, the wire (codec rev 8,
type 27) on both doors, coalescing, warm-up, and the donated sketch under
snapshot / delta / MOVE export. CPU, tiny geometry, seeded."""

import socket
import struct
import time

import numpy as np
import pytest

from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.server import TokenServer
from sentinel_tpu.cluster.server_native import (
    NativeTokenServer,
    native_available,
)
from sentinel_tpu.cluster.token_service import (
    ClusterParamFlowRule,
    DefaultTokenService,
    TokenService,
    params_batch_entry,
)
from sentinel_tpu.core import clock as clock_mod
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.engine import EngineConfig, TokenStatus
from sentinel_tpu.engine.param import (
    ParamConfig,
    _cms_flat,
    _param_decide_jax,
    fat_shape,
    make_param_state,
    make_param_step,
    pack_param_rows,
    packed_lines,
)
from sentinel_tpu.metrics.server import server_metrics

OK, BLOCKED, NO_RULE = (int(TokenStatus.OK), int(TokenStatus.BLOCKED),
                        int(TokenStatus.NO_RULE_EXISTS))
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=256)
PCFG = ParamConfig(max_param_rules=8, depth=4, width=512)
BUCKETS = (64, 256)
# rule -> (count, {value: item threshold}); value 77 is an item of both
# rules 1 and 2, with different thresholds
RULES = {1: (5.0, {11: 10.0, 77: 7.0}), 2: (3.0, {77: 2.0}), 3: (4.0, {})}


class Reference:
    """The semantics the batched entry documents, as plain Python: exact
    counts per (rule, value) in a window of ``n_buckets`` buckets; requests
    in order; a request passes only if every value has headroom, and the
    values that had it stay counted. (The benchmark keeps its own copy,
    ``cellbench/families/hotparam_reference.py``.)"""

    def __init__(self, rules, bucket_ms=500, n_buckets=2):
        self.rules, self.bucket_ms, self.n_buckets = rules, bucket_ms, n_buckets
        self.windows = {}

    def decide(self, t_ms, rule, acquire, values):
        if rule not in self.rules:
            return NO_RULE
        count, items = self.rules[rule]
        start = t_ms - t_ms % self.bucket_ms
        oldest = start - (self.n_buckets - 1) * self.bucket_ms
        every = True
        for v in values:
            w = self.windows.setdefault((rule, int(v)), {})
            total = sum(n for s, n in w.items() if s >= oldest)
            if total + acquire <= items.get(int(v), count):
                w[start] = w.get(start, 0) + acquire
            else:
                every = False
        return OK if every else BLOCKED


def make_service(**kw):
    svc = DefaultTokenService(CFG, param_config=PCFG, serve_buckets=BUCKETS,
                              fuse_depths=(), **kw)
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


@pytest.fixture(autouse=True)
def fresh_window(clock):
    clock.advance(2_000)  # whatever an earlier test counted has slid out


def ask(service, requests):
    """``requests``: ``[(rule, acquire, [values])]`` of one value count."""
    ids = np.array([r for r, _a, _v in requests], np.int64)
    acq = np.array([a for _r, a, _v in requests], np.int32)
    hashes = np.array([v for _r, _a, v in requests], np.int64)
    status, remaining, wait = service.request_params_batch(ids, acq, hashes)
    assert status.dtype == np.int8 and len(status) == len(requests)
    assert not remaining.any() and not wait.any()
    return status.tolist()


def now_of(service):
    return service._engine_now()


def seeded(n, k, seed):
    rng = np.random.default_rng(seed)
    rules = rng.choice([1, 2, 3, 9], n, p=[0.4, 0.3, 0.2, 0.1])  # 9: no rule
    return [(int(r), int(rng.integers(1, 3)),
             [int(v) for v in rng.choice([11, 77, 5, 6, 7, 8, 9], k,
                                         replace=False)])
            for r in rules]


SCENARIOS = {
    # 40 requests on one value: the first count pass, in batch order
    "in_batch_order": [(1, 1, [5])] * 40,
    # the item's threshold, beside a plain value of the same rule, and the
    # same hash as an item of another rule with another threshold
    "item_thresholds": ([(1, 1, [11])] * 12 + [(1, 1, [5])] * 7
                        + [(1, 1, [77])] * 9 + [(2, 1, [77])] * 4),
    "two_values": ([(3, 1, [5, 6])] * 3 + [(3, 1, [6, 7])] * 3
                   + [(3, 1, [7, 8])] * 4),
    "no_rule_rows": [(9, 1, [5]), (1, 1, [5]), (9, 1, [5]), (1, 1, [5])] * 4,
    "mixed_acquires": [(1, 2, [5]), (1, 3, [5]), (1, 1, [5]), (1, 1, [6]),
                       (1, 4, [6]), (1, 5, [6])],
    "padding_to_64": seeded(37, 1, 1),
    "padding_to_256": seeded(201, 1, 2),
    "a_full_bucket": seeded(256, 1, 3),
    "three_values_a_request": seeded(60, 3, 4),
    # past the largest bucket: cut into chunks of whole requests
    "past_the_largest_bucket": seeded(700, 1, 5),
    "two_values_past_the_largest_bucket": seeded(300, 2, 6),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_batched_verdicts_equal_the_reference(svc, name):
    requests = SCENARIOS[name]
    ref = Reference(RULES)
    t = now_of(svc)
    assert ask(svc, requests) == [ref.decide(t, *r) for r in requests]


def test_the_window_slides_on_a_driven_clock(svc, clock):
    ref = Reference(RULES)
    got, want = [], []
    for step_ms in (0, 400, 200, 300, 300, 500, 1100):
        clock.advance(step_ms)
        t = now_of(svc)
        batch = [(3, 1, [5])] * 3 + [(3, 1, [6])] * 2
        got += ask(svc, batch)
        want += [ref.decide(t, *r) for r in batch]
    assert got == want
    assert OK in got[5:10] and BLOCKED in got[5:10]  # both kinds were seen


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_a_batch_of_n_equals_n_single_calls_in_order(clock, seed):
    one, other = make_service(), make_service()
    try:
        requests = seeded(90, 2, seed)
        batch = ask(one, requests)
        singles = [int(other.request_params_token(r, a, v).status)
                   for r, a, v in requests]
        assert batch == singles
        assert {OK, BLOCKED, NO_RULE} <= set(batch)
    finally:
        one.close()
        other.close()


def test_the_one_request_entry_is_the_one_row_case(svc):
    before = server_metrics().param_totals()
    r = svc.request_params_token(1, 1, [5, 6])
    assert (r.status, r.remaining, r.wait_ms) == (TokenStatus.OK, 0, 0)
    assert svc.request_params_token(9, 1, [5]).status == \
        TokenStatus.NO_RULE_EXISTS
    assert svc.request_params_token(1, 1, []).status == TokenStatus.OK
    after = server_metrics().param_totals()
    grew = {k: after[k] - before[k] for k in after}
    assert grew == {"param_dispatch_total": 2, "param_requests_total": 2,
                    "param_values_total": 3, "param_blocked_total": 0,
                    "param_no_rule_total": 1}


def test_the_spi_default_asks_one_request_at_a_time():
    asked = []

    class Plain(TokenService):
        def request_params_token(self, flow_id, acquire, param_hashes):
            asked.append((flow_id, acquire, list(param_hashes)))
            from sentinel_tpu.cluster.token_service import TokenResult

            return TokenResult(TokenStatus.BLOCKED if flow_id == 2
                               else TokenStatus.OK)

    status, remaining, wait = Plain().request_params_batch(
        np.array([1, 2]), np.array([1, 3]), np.array([[5, 6], [7, 8]]))
    assert status.tolist() == [OK, BLOCKED] and asked == [
        (1, 1, [5, 6]), (2, 3, [7, 8])]

    class Forwarding:  # intercepts the one-request entry only
        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def request_params_token(self, flow_id, acquire, param_hashes):
            asked.append("wrapped")
            return self._inner.request_params_token(flow_id, acquire,
                                                    param_hashes)

    entry = params_batch_entry(Forwarding(Plain()))
    entry(np.array([1]), np.array([1]), np.array([[5]]))
    assert asked[-2] == "wrapped"
    assert params_batch_entry(Plain()).__self__.__class__ is Plain


def test_every_slot_of_a_batch_is_dirty(clock):
    service = make_service()
    try:
        service.replication_enable()
        ask(service, [(1, 1, [5]), (3, 1, [5]), (9, 1, [5]), (3, 1, [6])])
        slots = {service._param_rules[r][0] for r in (1, 3)}
        assert service._dirty["param"] == slots
    finally:
        service.close()


def test_nothing_compiles_after_warmup(clock):
    service = make_service()
    try:
        service.warmup()
        assert sorted(service._param_steps) == list(BUCKETS)
        sm = server_metrics()
        before = sm.compiles_after_warmup_total
        for n, k in ((1, 1), (64, 1), (65, 1), (256, 1), (100, 2), (600, 1)):
            ask(service, seeded(n, k, n))
        assert sm.compiles_after_warmup_total == before
        assert sm.param_impl == ("jax", "platform 'cpu': Mosaic compiles "
                                        "for TPU only")
        text = sm.render()
        assert "sentinel_server_param_dispatch_total" in text
        assert 'sentinel_server_param_impl_info{impl="jax"' in text
    finally:
        service.close()


def test_the_step_is_named_donates_its_state_and_packs_one_array():
    step = make_param_step(PCFG, 64, "jax")
    state = make_param_state(PCFG, flat=True)
    assert state.counts.shape == (8 * 2 * 4 * 512,)
    assert packed_lines(PCFG) == 3 + 4 + 2 + 1
    packed = pack_param_rows(
        PCFG, 64, [0, 0, -1], [1, 1, 1], [1.0, 1.0, 5.0],
        np.zeros((3, 4), np.int32), np.zeros((3, 2), np.int32), 1_000, 1, 3)
    assert packed.shape == (10, 64) and packed.dtype == np.int32
    lowered = step.lower(state, packed)
    assert "jit_param_decide_b64" in lowered.as_text()[:400]
    new_state, verdicts = step(state, packed)
    assert verdicts.shape == (3, 64)
    assert np.asarray(verdicts)[0, :3].tolist() == [OK, BLOCKED, NO_RULE]
    assert state.counts.is_deleted() and not new_state.counts.is_deleted()
    hlo = lowered.compile().as_text()
    for scope in ("param_roll", "param_estimate", "param_prefix",
                  "param_admit", "param_commit", "param_request_and",
                  "slim_pre", "slim_post"):
        assert scope in hlo, scope


def test_a_stale_bucket_is_cleared_once_and_only_its_plane():
    step = make_param_step(PCFG, 64, "jax")
    state = make_param_state(PCFG, flat=True)

    def cells(state):
        return np.asarray(state.counts).reshape(8, 2, 4, 512)

    def one(state, now, slot=0):
        packed = pack_param_rows(
            PCFG, 64, [slot], [1], [9.0], np.full((1, 4), 3, np.int32),
            np.zeros((1, 2), np.int32), now, 1, 1)
        return step(state, packed)[0]

    state = one(state, 100)          # bucket 0
    state = one(state, 600, slot=1)  # bucket 1
    counts = cells(state)
    assert counts[0, 0].sum() == 4 and counts[1, 1].sum() == 4
    state = one(state, 1_100)        # bucket 0 again: stale, cleared
    counts = cells(state)
    assert counts[0, 0].sum() == 4 and counts[1, 1].sum() == 4
    assert counts[:, 0].sum() == 4 and counts[:, 1].sum() == 4
    state = one(state, 1_200)        # same bucket: not cleared again
    assert cells(state)[0, 0].sum() == 8


def _flat_reference(counts, starts, slot, idx, acquire, threshold, valid,
                    now):
    """``_cms_flat`` as plain numpy on the same flat cells: the lazy roll,
    the windowed estimate, the in-batch admission (three passes over the
    earlier admitted rows of the same (rule, index tuple), as the step
    makes them) and the commit as one ``np.add.at``: an update a cell and
    lane, in batch order, duplicates and all."""
    n_p, n_b, depth, width = fat_shape(PCFG)
    counts, starts = counts.copy(), starts.copy()
    cur = (now // PCFG.bucket_ms) % n_b
    cur_start = now - now % PCFG.bucket_ms
    view = counts.reshape(n_p, n_b, depth, width)
    if starts[cur] != cur_start:
        view[:, cur] = 0
    starts[cur] = cur_start
    age = now - starts
    bucket_ok = (age >= 0) & (age < PCFG.interval_ms)
    live = valid & (slot >= 0)
    lanes = np.arange(depth)
    estimate = np.zeros(len(slot), np.int32)
    for i in np.flatnonzero(live):
        per = view[slot[i], :, lanes, idx[i]]  # [depth, buckets]
        estimate[i] = (per * bucket_ok[None, :]).sum(1).min()
    keys = [(int(slot[i]), *idx[i].tolist()) if live[i] else None
            for i in range(len(slot))]
    acq = acquire.astype(np.int32)
    admit = live.copy()
    for _ in range(3):
        seen, prefix = {}, np.zeros(len(slot), np.float32)
        for i, key in enumerate(keys):
            prefix[i] = seen.get(key, 0)
            if admit[i]:
                seen[key] = seen.get(key, 0) + int(acq[i])
        admit = live & (estimate.astype(np.float32) + prefix
                        + acq.astype(np.float32) <= threshold)
    cell = ((slot[:, None] * n_b + cur) * depth + lanes[None]) * width + idx
    np.add.at(counts, cell[admit].reshape(-1), np.repeat(acq[admit], depth))
    return counts, starts, admit


def _commit_batch(rows, seed):
    """``rows`` seeded rows over every rule slot at the tests' geometry
    (8 slots x 512 cells a lane: from 1,024 rows up most cells are hit by
    several rows), a tenth of them on one value."""
    rng = np.random.default_rng(seed)
    slot = rng.integers(0, PCFG.max_param_rules, rows).astype(np.int32)
    idx = rng.integers(0, PCFG.width, (rows, PCFG.depth)).astype(np.int32)
    crowd = rng.random(rows) < 0.1
    slot[crowd], idx[crowd] = 5, idx[0]
    return dict(slot=slot, idx=idx,
                acquire=np.ones(rows, np.int32),
                threshold=np.full(rows, 6.0, np.float32),
                valid=np.ones(rows, bool))


def _commit_cases():
    def batch(slot, idx, acquire=1, threshold=50.0, valid=True):
        n = len(slot)
        return dict(
            slot=np.asarray(slot, np.int32),
            idx=np.asarray(idx, np.int32).reshape(n, PCFG.depth),
            acquire=np.broadcast_to(np.int32(acquire), (n,)).copy(),
            threshold=np.broadcast_to(np.float32(threshold), (n,)).copy(),
            valid=np.broadcast_to(np.bool_(valid), (n,)).copy())

    rng = np.random.default_rng(39)
    one, other = [1, 7, 3, 4], [2, 7, 5, 6]  # they meet in lane 1 alone
    mixed = _commit_batch(64, 1)
    mixed["acquire"] = rng.integers(1, 6, 64).astype(np.int32)
    mixed["threshold"][:] = 11.0
    padded = _commit_batch(64, 2)
    padded["slot"][40:] = -1           # padding, as pack_param_rows leaves it
    padded["valid"][::3] = False
    padded["valid"][40:] = False
    cases = {
        # 64 rows on one (rule, value): 40 pass, every lane's cell reads 40
        "many_rows_on_one_value": [(100, batch([2] * 64, [one] * 64,
                                               threshold=40.0))],
        "two_values_meet_in_one_lane": [(100, batch(
            [3] * 20, [one, other] * 10, acquire=2))],
        "every_row_refused": [(100, batch([1] * 64, [one] * 64,
                                          threshold=0.0))],
        "padding_and_invalid_rows": [(100, padded)],
        "acquires_above_one": [(100, mixed)],
        # bucket 0, bucket 1, then bucket 0 again: stale, rolled, committed
        "across_a_bucket_boundary": [(100, _commit_batch(64, 3)),
                                     (600, _commit_batch(64, 4)),
                                     (1_100, _commit_batch(64, 5)),
                                     (1_200, _commit_batch(64, 6))],
        "rows_64": [(100, _commit_batch(64, 7))] * 2,
        "rows_1024": [(100, _commit_batch(1024, 8))] * 2,
        "rows_4096": [(100, _commit_batch(4096, 9))] * 2,
    }
    return cases


@pytest.mark.parametrize("entry", ["flat", "4d"])
@pytest.mark.parametrize("case", sorted(_commit_cases()))
def test_the_commit_leaves_the_cells_np_add_at_leaves(case, entry):
    """ISSUE 39: the commit writes each touched cell once, from a sorted,
    pre-reduced, duplicate-free batch; the cells it leaves are bit for bit
    what a serial add of every admitted (row, lane) leaves, through the flat
    core and through the 4-D entry ``_param_decide_jax`` alike."""
    import jax
    from functools import partial

    if entry == "flat":
        core = jax.jit(partial(_cms_flat, PCFG))
    want_counts = np.zeros(int(np.prod(fat_shape(PCFG))), np.int32)
    want_starts = np.asarray(make_param_state(PCFG).starts)
    got_counts, got_starts = want_counts.copy(), want_starts.copy()
    for now, rows in _commit_cases()[case]:
        want_counts, want_starts, want_admit = _flat_reference(
            want_counts, want_starts, now=now, **rows)
        args = (rows["slot"], rows["idx"], rows["acquire"],
                rows["threshold"], rows["valid"], np.int32(now))
        if entry == "flat":
            got_counts, got_starts, admit, _est = core(
                got_counts, got_starts, *args)
        else:
            state = make_param_state(PCFG)._replace(
                starts=got_starts,
                counts=got_counts.reshape(fat_shape(PCFG)))
            state, admit, _est = _param_decide_jax(PCFG, state, *args)
            got_counts, got_starts = state.counts.reshape(-1), state.starts
        got_counts, got_starts = np.asarray(got_counts), np.asarray(got_starts)
        assert np.asarray(admit).tolist() == want_admit.tolist()
        assert got_starts.tolist() == want_starts.tolist()
        assert got_counts.dtype == np.int32
        assert np.array_equal(got_counts, want_counts)
    assert case == "every_row_refused" or want_counts.any()


def test_the_donated_sketch_survives_exports_between_dispatches(clock):
    from sentinel_tpu.cluster.rebalance import (
        decode_move_state_blob,
        encode_move_state_blob,
    )
    from sentinel_tpu.ha import replication as R

    src, twin = make_service(), make_service()
    try:
        src.replication_enable()
        ref = Reference(RULES)
        t = now_of(src)
        got, want = [], []

        def both(requests):
            got.extend(ask(src, requests))
            want.extend(ref.decide(t, *r) for r in requests)

        both([(3, 1, [5])] * 2)
        snapshot = R.decode_snapshot_blob(
            R.encode_snapshot_blob(src.export_state()))
        both([(3, 1, [5])] * 1)
        delta = R.decode_delta_blob(R.encode_delta_blob(src.export_delta()))
        both([(3, 1, [5])] * 1)
        doc = decode_move_state_blob(
            encode_move_state_blob(src.export_namespace_state("ns1")))
        both([(3, 1, [5])] * 3 + [(3, 1, [6])] * 2)
        assert got == want and BLOCKED in got
        assert "param_slim" in delta and doc["param_fids"]
        # the snapshot taken after two tokens carries them: a twin that
        # imports it passes two more of the count of four, not four
        twin.import_state(snapshot)
        assert ask(twin, [(3, 1, [5])] * 4) == [OK, OK, BLOCKED, BLOCKED]
    finally:
        src.close()
        twin.close()


# -- the wire -----------------------------------------------------------------
def test_the_batch_param_codec_round_trips():
    rng = np.random.default_rng(7)
    for n, k in ((1, 1), (300, 1), (40, 3), (0, 2),
                 (P.max_param_rows_per_frame(255), 255)):
        ids = rng.integers(-2**62, 2**62, n)
        counts = rng.integers(1, 9, n).astype(np.int32)
        hashes = rng.integers(-2**63, 2**63 - 1, (n, k))
        frame = P.encode_batch_param_request(99, ids, counts, hashes)
        (flen,) = struct.unpack_from(">H", frame)
        assert flen == len(frame) - 2 == 5 + 3 + n * (13 + 8 * k)
        assert P.peek_type(frame[2:]) == P.MsgType.BATCH_PARAM_FLOW == 27
        xid, i2, c2, p2, h2 = P.decode_batch_param_request(frame[2:])
        assert xid == 99 and (i2 == ids).all() and (c2 == counts).all()
        assert not p2.any() and h2.shape == (n, k) and (h2 == hashes).all()
    assert P.max_param_rows_per_frame(1) == 3120
    assert P.WIRE_REV >= 8 and 27 in P.KNOWN_TYPES
    rsp = P.encode_batch_response(5, [0, 1, 3], [0, 0, 0], [0, 0, 0],
                                  msg_type=P.MsgType.BATCH_PARAM_FLOW)
    assert P.peek_type(rsp[2:]) == 27
    assert P.decode_batch_response(rsp[2:])[1].tolist() == [0, 1, 3]


@pytest.mark.parametrize("what", ["no_values", "too_many_values",
                                  "too_many_rows", "short_body", "runt",
                                  "rows_without_a_value"])
def test_malformed_param_batches_are_refused_by_the_codec(what):
    ids, counts = np.arange(4), np.ones(4, np.int32)
    if what == "no_values":
        with pytest.raises(ValueError):
            P.encode_batch_param_request(1, ids, counts, np.zeros((4, 0)))
    elif what == "too_many_values":
        with pytest.raises(ValueError):
            P.encode_batch_param_request(1, ids, counts, np.zeros((4, 256)))
    elif what == "too_many_rows":
        with pytest.raises(ValueError):
            P.encode_batch_param_request(1, np.arange(3121),
                                         np.ones(3121, np.int32),
                                         np.zeros((3121, 1)))
    else:
        frame = P.encode_batch_param_request(1, ids, counts,
                                             np.zeros((4, 2)))[2:]
        bad = {"short_body": frame[:-1], "runt": frame[:6],
               "rows_without_a_value": frame[:7] + b"\x00" + frame[8:]}[what]
        with pytest.raises(ValueError):
            P.decode_batch_param_request(bad)


def raw_exchange(port, payloads, n_frames, timeout=10.0):
    """Send ``payloads`` on one connection, collect ``n_frames`` response
    frames; ``None`` when the server closed the connection first."""
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as s:
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        for p in payloads:
            s.sendall(p)
        reader, frames = P.FrameReader(), []
        deadline = time.monotonic() + timeout
        while len(frames) < n_frames and time.monotonic() < deadline:
            try:
                data = s.recv(1 << 16)
            except socket.timeout:
                break
            if not data:
                return None
            frames += reader.feed(data)
        return frames


needs_native = pytest.mark.skipif(not native_available(),
                                  reason="native library not built")


@pytest.fixture(scope="module", params=["native", "asyncio"])
def door(request, svc):
    if request.param == "native":
        if not native_available():
            pytest.skip("native library not built")
        server = NativeTokenServer(svc, port=0, max_batch=256)
    else:
        server = TokenServer(svc, port=0)
    server.start()
    yield server
    server.stop()


def test_both_doors_answer_both_frames_through_the_batched_entry(door, svc):
    client = TokenClient("127.0.0.1", door.port, timeout_ms=10_000)
    try:
        ref = Reference(RULES)
        t = now_of(svc)
        requests = seeded(150, 2, 21)
        out = client.request_params_batch(
            [r for r, _a, _v in requests], [a for _r, a, _v in requests],
            [v for _r, _a, v in requests])
        assert out is not None
        assert out[0].tolist() == [ref.decide(t, *r) for r in requests]
        singles = [(3, 1, [901]), (3, 1, [901, 902]), (9, 1, [901])]
        got = [int(client.request_params_token(*r).status) for r in singles]
        assert got == [ref.decide(t, *r) for r in singles]
    finally:
        client.close()


def test_both_doors_close_on_a_malformed_param_batch_and_serve_on(door):
    good = P.encode_batch_param_request(3, [3], [1], [[911]])
    body = good[2:]
    no_value = body[:7] + b"\x00" + body[8:]
    short = P.encode_batch_param_request(4, [3, 3], [1, 1],
                                         [[1], [2]])[2:-5]
    for bad in (no_value, short):
        frame = struct.pack(">H", len(bad)) + bad
        assert raw_exchange(door.port, [frame], 1, timeout=3.0) is None
    empty = P.encode_batch_param_request(6, [], [], np.zeros((0, 2)))
    frames = raw_exchange(door.port, [empty, good], 2)
    assert frames is not None and len(frames) == 2
    by_xid = {P.peek_xid(f): f for f in frames}
    assert P.peek_type(by_xid[6]) == 27
    assert P.decode_batch_response(by_xid[6])[1].tolist() == []
    assert P.decode_batch_response(by_xid[3])[1].tolist() == [OK]


@needs_native
def test_frames_of_several_connections_coalesce_into_fewer_dispatches(
        svc, clock):
    server = NativeTokenServer(svc, port=0, max_batch=256)
    server.start()
    try:
        n_conn, per_conn, rows = 3, 8, 16
        socks = [socket.create_connection(("127.0.0.1", server.port),
                                          timeout=10) for _ in range(n_conn)]
        before = server_metrics().param_totals()
        xid = 1
        for j in range(per_conn):
            for s in socks:
                s.sendall(P.encode_batch_param_request(
                    xid, np.full(rows, 1), np.ones(rows, np.int32),
                    1000 + np.arange(rows).reshape(rows, 1) + 16 * xid))
                xid += 1
        answered = 0
        for s in socks:
            reader, got = P.FrameReader(), []
            while len(got) < per_conn:
                got += reader.feed(s.recv(1 << 16))
            for f in got:
                assert P.peek_type(f) == 27
                assert P.decode_batch_response(f)[1].tolist() == [OK] * rows
            answered += len(got)
            s.close()
        after = server_metrics().param_totals()
        assert answered == n_conn * per_conn
        assert (after["param_requests_total"]
                - before["param_requests_total"]) == answered * rows
        dispatches = (after["param_dispatch_total"]
                      - before["param_dispatch_total"])
        assert 0 < dispatches < answered
    finally:
        server.stop()


@needs_native
def test_single_param_frames_are_decided_a_drained_queue_at_a_time(
        svc, clock):
    server = NativeTokenServer(svc, port=0, max_batch=256)
    server.start()
    try:
        ref = Reference(RULES)
        t = now_of(svc)
        requests = ([(3, 1, [921])] * 6 + [(3, 1, [921, 922])]
                    + [(3, 1, [922])] * 5 + [(9, 1, [1])] + [(3, 1, [])])
        blob = b"".join(
            P.encode_request(P.FlowRequest(
                100 + i, r, a, False, P.MsgType.PARAM_FLOW, tuple(v)))
            for i, (r, a, v) in enumerate(requests))
        before = server_metrics().param_totals()
        frames = raw_exchange(server.port, [blob], len(requests))
        after = server_metrics().param_totals()
        got = {r.xid: r.status for r in map(P.decode_response, frames)}
        want = [OK if not v else ref.decide(t, r, a, v)
                for r, a, v in requests]
        assert [got[100 + i] for i in range(len(requests))] == want
        dispatches = (after["param_dispatch_total"]
                      - before["param_dispatch_total"])
        assert 0 < dispatches < len(requests) - 1
    finally:
        server.stop()
