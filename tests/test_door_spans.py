"""The door's two halves (PR 38): a data frame is stamped on the C++ door's
IO thread when its last byte is read, the stamp rides the pull and comes back
with the verdicts, and the IO thread closes the span when ``send()`` has taken
the last byte of the reply. Through the real native door over loopback, on
the CPU: ``door_in_ms``, ``door_wake_ms`` (the GIL's), ``queue_wait_ms`` on
the native lane, ``door_out_ms``, ``door_residence_ms`` (the server's own
verdict latency), and the recorder's ``rx`` and ``reply_taken``.
"""

import socket
import sys
import time

import numpy as np
import pytest

from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.server_native import (
    NativeTokenServer,
    native_available,
)
from sentinel_tpu.cluster.token_service import (
    DefaultTokenService,
    TokenService,
)
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.metrics.histogram import LatencyHistogram
from sentinel_tpu.metrics.server import ServerMetrics, server_metrics
from sentinel_tpu.trace import ring, spans

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native library not built")

CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=64)
SM = server_metrics()
DOOR = ("door_in_ms", "door_out_ms", "door_residence_ms")
NEW = DOOR + ("door_wake_ms", "queue_wait_ms")
FRAMES = 40


def _service():
    svc = DefaultTokenService(CFG)
    svc.load_rules([
        ClusterFlowRule(flow_id=i, count=50.0, mode=ThresholdMode.GLOBAL)
        for i in range(1, 9)])
    return svc


class SleepyService(TokenService):
    """A foreign service (no dispatch/materialize split): every row OK,
    after ``delay_s`` inside the device lane's call."""

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s

    def request_batch_arrays(self, flow_ids, counts, prios=None):
        if self.delay_s:
            time.sleep(self.delay_s)
        n = len(flow_ids)
        return (np.zeros(n, np.int8), np.asarray(counts, np.int32),
                np.zeros(n, np.int32))


def _stages():
    return SM.stage_snapshot()


def _counts(names=NEW + ("intake_ms", "dispatch_ms")):
    snap = _stages()
    return {k: snap[k]["count"] for k in names}


def _settled(read, want, timeout=10.0):
    """The IO thread counts a frame's spans after ``send()`` returned: the
    client may hold the reply a moment before the counters do."""
    deadline = time.monotonic() + timeout
    got = read()
    while got < want and time.monotonic() < deadline:
        time.sleep(0.002)
        got = read()
    return got


def _residence_count():
    return _stages()["door_residence_ms"]["count"]


def _frame(xid, n=4):
    return P.encode_batch_request(
        xid, np.arange(1, n + 1, dtype=np.int64), np.ones(n, np.int32),
        np.zeros(n, bool))


def _read_reply(sock, n=4):
    need, buf = 2 + 5 + 2 + 9 * n, b""
    while len(buf) < need:
        chunk = sock.recv(need - len(buf))
        assert chunk, "door closed the connection"
        buf += chunk
    return buf


def _roundtrip(sock, xid, n=4):
    sock.sendall(_frame(xid, n))
    return _read_reply(sock, n)


def _mean(after, before, name):
    n = after[name]["count"] - before[name]["count"]
    return (after[name]["sum"] - before[name]["sum"]) / n


# -- the raw door: every stamp of one frame ------------------------------------
@pytest.fixture
def door():
    from sentinel_tpu.native.lib import Frontdoor

    d = Frontdoor("127.0.0.1", 0)
    for name in d.SPANS:
        d.set_span_bounds(name, LatencyHistogram(lo=0.001, hi=10_000.0).bounds)
    cap = d.arena_cap
    block = dict(
        ids=np.empty(cap, np.int64), counts=np.empty(cap, np.int32),
        prios=np.empty(cap, np.uint8), hashes=np.empty(cap, np.int64),
        f_rx_ns=np.zeros(cap, np.int64), wake_ns=np.zeros(1, np.int64),
        **{k: np.empty(cap, np.uint8 if k == "f_type" else np.int32)
           for k in ("f_fd", "f_gen", "f_xid", "f_n", "f_type")})
    sock = socket.create_connection(("127.0.0.1", d.port))
    sock.settimeout(10)
    yield d, block, sock
    sock.close()
    d.stop()


def _pull(d, block):
    got = None
    deadline = time.monotonic() + 10
    while got is None and time.monotonic() < deadline:
        got = d.wait_any_into(block, timeout_ms=100)
    assert got is not None
    n, k, _nv = got
    frames = tuple(block[c][:k].copy() for c in (
        "f_fd", "f_gen", "f_xid", "f_n", "f_type", "f_rx_ns"))
    return n, frames


def _answer(d, n, frames):
    d.submit(frames, np.zeros(n, np.int8), np.zeros(n, np.int32),
             np.zeros(n, np.int32))


def test_one_frames_stamps_are_in_order_on_monotonic_ns(door):
    d, block, sock = door
    t_send = time.monotonic_ns()
    sock.sendall(_frame(7))
    n, frames = _pull(d, block)
    t_py = time.monotonic_ns()
    rx_ns, wake_ns = int(frames[5][0]), int(block["wake_ns"][0])
    t_before = time.monotonic_ns()
    _answer(d, n, frames)
    _read_reply(sock, n)
    t_after = time.monotonic_ns()
    assert _settled(lambda: d.span_stats()["door_residence_ms"][0], 1) == 1
    t_counted = time.monotonic_ns()
    stats = d.span_stats()
    # one frame: its sums are its spans, and give back the door's stamps
    done_ns = rx_ns + round(stats["door_residence_ms"][1] * 1e6)
    submit_ns = done_ns - round(stats["door_out_ms"][1] * 1e6)
    assert t_send <= rx_ns <= wake_ns <= t_py <= t_before
    # the IO thread stamps a frame done after send() returned, and the reply
    # may be read before that: what bounds done_ns is the count that follows
    # it, not the reply (7 of 3,000 frames read done_ns 0.1-158 us past
    # t_after on a loaded machine)
    assert t_before <= submit_ns <= done_ns <= t_counted
    assert submit_ns <= t_after
    assert stats["door_in_ms"][0] == 1
    assert round(stats["door_in_ms"][1] * 1e6) == wake_ns - rx_ns
    for name in DOOR:  # the bucket counts are of the one frame
        assert int(stats[name][3].sum()) == 1


@pytest.mark.parametrize("how", ["stamp_0", "five_columns"])
def test_a_frame_without_a_stamp_counts_nothing_on_the_way_out(door, how):
    d, block, sock = door
    sock.sendall(_frame(9))
    n, frames = _pull(d, block)
    if how == "stamp_0":
        frames = frames[:5] + (np.zeros_like(frames[5]),)
    else:
        frames = frames[:5]
    _answer(d, n, frames)
    _read_reply(sock, n)
    # a stamped frame behind it shows the IO thread has counted past it
    sock.sendall(_frame(10))
    n, frames = _pull(d, block)
    _answer(d, n, frames)
    _read_reply(sock, n)
    assert _settled(lambda: d.span_stats()["door_residence_ms"][0], 1) == 1
    stats = d.span_stats()
    assert stats["door_out_ms"][0] == 1
    assert stats["door_in_ms"][0] == 2  # counted at the pull, both frames


# -- the served path -------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    """One native server over the real service, driven a frame at a time,
    disarmed then armed: the counts and the recorder's events of each."""
    ring.reset_for_tests()
    svc = _service()
    server = NativeTokenServer(svc, port=0, idle_ttl_s=None)
    server.start()
    client = TokenClient("127.0.0.1", server.port, timeout_ms=5000)
    ids = np.arange(1, 9, dtype=np.int64)
    run = {}
    try:
        base, accounted = _residence_count(), SM.account_ms.count
        assert client.request_batch_arrays(ids) is not None  # settle
        assert _settled(_residence_count, base + 1) == base + 1
        c0, s0 = _counts(), _stages()
        for _ in range(FRAMES):
            assert client.request_batch_arrays(ids) is not None
        _settled(_residence_count, c0["door_residence_ms"] + FRAMES)
        run["grew"] = {k: v - c0[k] for k, v in _counts().items()}
        run["before"], run["after"] = s0, _stages()
        run["disarmed_events"] = ring.events(
            stages={ring.RX, ring.REPLY_TAKEN})
        # the reply lane answers first and counts after: the last disarmed
        # frame's account half must be through before the recorder is armed,
        # or its DEVICE_OUT counts among the armed frames' below and the
        # recorder is disarmed before the last armed frame's reply_out (seen
        # on a loaded machine)
        _settled(lambda: SM.account_ms.count, accounted + 1 + FRAMES)
        ring.arm()
        t_armed = time.monotonic_ns()
        for _ in range(FRAMES):
            assert client.request_batch_arrays(ids) is not None
        _settled(lambda: len(ring.events(stages={ring.DEVICE_OUT})), FRAMES)
        ring.disarm()
        run["t_armed"] = t_armed
        run["events"] = ring.events(since_ns=0)
        run["phases"] = spans.dispatch_phases()
        run["span"] = spans.assemble(ring.sampled_xids(1)[0])
    finally:
        ring.disarm()
        client.close()
        server.stop()
        svc.close()
    run["stopped"] = _stages()
    return run


@pytest.mark.parametrize("name", NEW)
def test_every_new_histogram_counts_once_per_frame_or_pull(served, name):
    # a frame at a time: one frame is one pull is one dispatch
    assert served["grew"][name] == FRAMES
    assert served["grew"]["intake_ms"] == FRAMES


def test_queue_wait_has_one_record_per_pull_on_the_native_lane(served):
    assert served["grew"]["queue_wait_ms"] == served["grew"]["intake_ms"]
    assert served["grew"]["queue_wait_ms"] == served["grew"]["dispatch_ms"]


def test_the_phases_tile_the_residence(served):
    a, b = served["before"], served["after"]
    parts = sum(_mean(b, a, k) for k in (
        "door_in_ms", "door_wake_ms", "intake_ms", "queue_wait_ms",
        "dispatch_ms", "reply_queue_wait_ms", "decide_ms", "door_out_ms"))
    whole = _mean(b, a, "door_residence_ms")
    # what is left is the reply lane's slicing and the submit call
    assert 0 <= whole - parts <= _mean(b, a, "write_ms") + 0.05
    assert whole - parts < 0.5 * whole


def test_the_residence_bucket_counts_sum_to_the_count(served):
    for snap in (served["after"], served["stopped"]):
        res = snap["door_residence_ms"]
        assert res["cum"][-1] == res["count"]
        assert len(res["cum"]) == len(res["le"]) + 1
        assert all(x <= y for x, y in zip(res["cum"], res["cum"][1:]))
    # only this histogram carries them
    assert "cum" not in served["after"]["door_out_ms"]


def test_what_the_doors_counted_stays_after_the_server_stopped(served):
    for name in DOOR:
        assert served["stopped"][name]["count"] >= (
            served["after"][name]["count"] + FRAMES)


def test_disarmed_the_recorder_writes_nothing_for_the_new_stages(served):
    assert served["disarmed_events"] == []


def test_armed_a_frames_span_starts_at_rx(served):
    span = served["span"]
    assert span["stages"][:3] == ["rx", "client_in", "enqueue"]
    assert span["complete"]
    rx = [e for e in served["events"] if e["stage"] == "rx"]
    assert len(rx) == FRAMES
    by_xid = {}
    for e in served["events"]:
        if e["xid"]:
            by_xid.setdefault(e["xid"], {})[e["stage"]] = e["t_ns"]
    # (the last disarmed frame's reply lane may find the recorder armed by
    # the time it has submitted, and leave a lone reply_out)
    armed = [stamps for stamps in by_xid.values() if "rx" in stamps]
    assert len(armed) == FRAMES
    for stamps in armed:  # back-dated to the door's own stamp
        assert stamps["rx"] <= stamps["client_in"] <= stamps["reply_out"]


def test_dispatch_phases_split_the_wait_at_reply_taken(served):
    taken = [e for e in served["events"] if e["stage"] == "reply_taken"]
    assert len(taken) == FRAMES
    assert all(e["xid"] == 0 and e["thread"].startswith(
        "sentinel-native-reply") for e in taken)
    rows = [d for d in served["phases"] if d["complete"]
            and d["startNs"] >= served["t_armed"]]
    assert len(rows) >= FRAMES - 1
    for d in rows:
        assert d["replyQueueWaitMs"] >= 0 and d["deviceWaitMs"] >= 0
        assert d["replyQueueWaitMs"] + d["deviceWaitMs"] == pytest.approx(
            d["waitMs"])


def test_off_the_lane_the_two_halves_are_none():
    ring.reset_for_tests()
    svc = _service()
    ring.arm()
    try:
        svc.request_batch_arrays(np.arange(1, 9, dtype=np.int64))
        rows = spans.dispatch_phases()
    finally:
        ring.disarm()
        svc.close()
    assert rows and rows[-1]["waitMs"] is not None
    assert rows[-1]["replyQueueWaitMs"] is None
    assert rows[-1]["deviceWaitMs"] is None


def test_record_many_takes_a_stamp_per_xid():
    ring.reset_for_tests()
    ring.arm()
    try:
        ring.record_many(ring.RX, [11, 12, 13], aux=3, t_ns=[500, 0, 700])
        got = ring.events(stages={ring.RX})
    finally:
        ring.reset_for_tests()
    # a stamp of 0 (a door that does not stamp) leaves the xid out
    assert [(e["xid"], e["t_ns"]) for e in got] == [(11, 500), (13, 700)]


# -- what the spans are for ----------------------------------------------------
@pytest.fixture
def sleepy():
    def start(delay_s):
        server = NativeTokenServer(
            SleepyService(delay_s), port=0, idle_ttl_s=None)
        server.start()
        sock = socket.create_connection(("127.0.0.1", server.port))
        sock.settimeout(20)
        started.append((server, sock))
        return server, sock

    started = []
    yield start
    for server, sock in started:
        sock.close()
        server.stop()


def test_a_service_that_sleeps_is_in_the_residence_not_in_door_out(sleepy):
    _server, sock = sleepy(0.03)
    base = _residence_count()
    _roundtrip(sock, 1)
    assert _settled(_residence_count, base + 1) == base + 1
    before = _stages()
    for xid in range(2, 7):
        _roundtrip(sock, xid)
    _settled(_residence_count, before["door_residence_ms"]["count"] + 5)
    after = _stages()
    assert after["door_residence_ms"]["count"] == (
        before["door_residence_ms"]["count"] + 5)
    assert _mean(after, before, "door_residence_ms") >= 30.0
    assert _mean(after, before, "door_out_ms") < 30.0
    assert _mean(after, before, "dispatch_ms") >= 30.0  # where it slept


# a client with no part in this process's GIL: once connected it says so, is
# told the first instant, and sends one frame at each agreed instant of the
# machine's CLOCK_MONOTONIC and reads its reply
_TIMED_CLIENT = """
import socket, sys, time
port, gap, n, need = (int(a) for a in sys.argv[1:5])
frame = bytes.fromhex(sys.argv[5])
s = socket.create_connection(("127.0.0.1", port))
s.settimeout(20)
print("ready", flush=True)
t_first = int(sys.stdin.readline())
for i in range(n):
    while time.monotonic_ns() < t_first + i * gap:
        time.sleep(0.0002)
    s.sendall(frame)
    buf = b""
    while len(buf) < need:
        buf += s.recv(need - len(buf))
"""


def test_a_held_gil_shows_in_door_wake_and_not_in_door_in():
    import subprocess

    # the intake lane stays parked in its C wait between frames (its poll
    # is the shutdown's granularity only)
    server = NativeTokenServer(SleepyService(), port=0, idle_ttl_s=None,
                               intake_timeout_ms=3000)
    server.start()
    n, gap = 12, 80_000_000
    child = subprocess.Popen(
        [sys.executable, "-c", _TIMED_CLIENT, str(server.port), str(gap),
         str(2 * n), str(2 + 5 + 2 + 9 * 4), _frame(5).hex()],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    assert child.stdout.readline().strip() == "ready"
    t_first = time.monotonic_ns() + 100_000_000
    child.stdin.write(f"{t_first}\n")
    child.stdin.flush()

    def idle(until_ns):  # gives the GIL away
        time.sleep(max(0.0, (until_ns - time.monotonic_ns()) * 1e-9))

    def hold(until_ns):  # pure Python: gives the GIL up only when asked to
        while time.monotonic_ns() < until_ns:
            pass

    def phase(first, around):
        """``n`` frames arrive at their instants while this thread spends
        from 15 ms before each to 35 ms after it in ``around``. The medians
        over the frames of ``door_wake_ms`` and ``door_in_ms``: one pull
        the machine's scheduler held up is not the GIL's doing."""
        wakes, ins = [], []
        last = _stages()
        for i in range(first, first + n):
            idle(t_first + i * gap - 15_000_000)
            around(t_first + i * gap + 35_000_000)
            now = _stages()
            if now["door_wake_ms"]["count"] - last["door_wake_ms"]["count"] == 1:
                wakes.append(_mean(now, last, "door_wake_ms"))
                ins.append(_mean(now, last, "door_in_ms"))
            last = now
        assert len(wakes) >= n // 2  # a frame may come late under load
        return float(np.median(wakes)), float(np.median(ins))

    old = sys.getswitchinterval()
    sys.setswitchinterval(0.005)
    try:
        wake_free, in_free = phase(0, idle)
        wake_held, in_held = phase(n, hold)
        assert child.wait(timeout=30) == 0
    finally:
        sys.setswitchinterval(old)
        child.kill()
        server.stop()
    # a lane back from its C call waits for the holder to be asked to let
    # go (the switch interval); the frame's way to the pull is C on the
    # door's own threads and waits for nobody. Relative facts only: what a
    # wake-up costs in ms is the machine's and its load's
    assert wake_held > 5 * wake_free, (wake_free, wake_held, in_free, in_held)
    assert in_held < wake_held / 3, (wake_free, wake_held, in_free, in_held)


def test_a_pull_the_intake_lane_answers_itself_counts_nothing():
    svc = _service()
    server = NativeTokenServer(svc, port=0, idle_ttl_s=None,
                               standby_of="primary")
    server.start()
    sock = socket.create_connection(("127.0.0.1", server.port))
    sock.settimeout(10)
    try:
        before = _counts()
        for xid in range(1, 6):
            reply = _roundtrip(sock, xid)
            assert reply[9] == 9  # TokenStatus.STANDBY, first row
    finally:
        sock.close()
        server.stop()  # joins the door's threads: their counts are final
        svc.close()
    grew = {k: v - before[k] for k, v in _counts().items()}
    assert grew["door_out_ms"] == 0 and grew["door_residence_ms"] == 0
    assert grew["queue_wait_ms"] == 0 and grew["intake_ms"] == 0
    assert grew["door_wake_ms"] == 5  # it was pulled, and by the GIL's leave


# -- the registry ---------------------------------------------------------------
def test_the_registry_folds_door_counts_by_difference():
    metrics = ServerMetrics()
    bounds = metrics.door_out_ms.bounds
    state = {"n": 0, "max": 0.5}

    def reader():
        counts = np.zeros(len(bounds) + 1, np.int64)
        counts[10] = state["n"]
        return {"door_out_ms": (
            state["n"], 0.5 * state["n"], state["max"], counts)}

    metrics.register_door_spans(reader)
    state["n"] = 4
    assert metrics.stage_snapshot()["door_out_ms"]["count"] == 4
    state["n"] = 10
    assert metrics.snapshot()["stages"]["door_out_ms"]["count"] == 10
    assert metrics.door_out_ms.sum == pytest.approx(5.0)
    assert metrics.door_out_ms.snapshot()["max"] == 0.5
    metrics.reset()  # what was counted so far goes with the reset
    state["n"] = 13
    assert metrics.stage_snapshot()["door_out_ms"]["count"] == 3
    # the door's max is since its start: it comes back only where it grew
    assert metrics.door_out_ms.snapshot()["max"] == 0.0
    state["n"], state["max"] = 15, 0.9
    metrics.unregister_door_spans(reader)  # folds once more, then lets go
    state["n"] = 99
    assert metrics.stage_snapshot()["door_out_ms"]["count"] == 5
    assert metrics.door_out_ms.snapshot()["max"] == 0.9
    assert 'sentinel_server_door_out_ms_count 5' in metrics.render()
