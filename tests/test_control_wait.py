"""The native server's control thread sleeps on the doors' bell (PR 51).

``NativeTokenServer._control_loop`` used to wake the interpreter every 2 ms
to ask every door for a control event. It now sleeps in native code, the GIL
released, on one bell a server (``native.lib.Bell``) that every door rings
after a push to its control queue: handled a millisecond after it arrives
(the wait's settle, the poll's mean), and ten wake-ups a second when nothing
does. Every wait in this file has its own deadline (a
socket time-out, a join time-out, a bounded loop), so no case can hang.
"""

import socket
import statistics
import threading
import time

import pytest

from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster import server_native
from sentinel_tpu.cluster.server_native import (
    NativeTokenServer,
    native_available,
)
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.metrics.server import server_metrics
from sentinel_tpu.native import lib as native_lib

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native library not built"
)

CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=256)
CONTROL_THREAD = "sentinel-native-control"
# a frame the bell woke the thread for is answered well inside this; one
# that waited for the 100 ms time-out is not, four times in five
PROMPT_S = 0.020


def _mostly_prompt(took):
    """The median under ``PROMPT_S``: half the frames and more were answered
    at once, which the time-out alone gives once in a hundred thousand sets
    of fifteen, and a loaded machine's stragglers do not undo."""
    return statistics.median(took) < PROMPT_S


@pytest.fixture(scope="module")
def svc():
    """One service for the file's servers, one after another: a restart
    finds its steps compiled (``stop()`` closes it, ``start()`` reopens)."""
    service = DefaultTokenService(CFG)
    service.load_rules([
        ClusterFlowRule(flow_id=2, count=1e9, mode=ThresholdMode.GLOBAL),
    ])
    return service


def _started(service, **kw):
    server = NativeTokenServer(service, port=0, idle_ttl_s=None, **kw)
    server.start()
    return server


@pytest.fixture()
def server(svc):
    srv = _started(svc)
    yield srv
    srv.stop()


def _connect(port):
    sock = socket.create_connection(("127.0.0.1", port), timeout=5)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.settimeout(5)
    return sock


def _ping_s(sock, xid):
    """One PING round trip on a raw socket, in seconds."""
    frame = P.encode_request(P.Ping(xid))
    t0 = time.perf_counter()
    sock.sendall(frame)
    head = sock.recv(2, socket.MSG_WAITALL)
    body = sock.recv(int.from_bytes(head, "big"), socket.MSG_WAITALL)
    took = time.perf_counter() - t0
    assert int.from_bytes(body[:4], "big") == xid
    return took


def _asleep():
    """Long enough for the control thread to be back in its wait."""
    time.sleep(0.03)


def _control_threads():
    return [t for t in threading.enumerate() if t.name == CONTROL_THREAD]


def _idle_wakeups_in(seconds):
    sm = server_metrics()
    before = sm.control_totals()
    time.sleep(seconds)
    after = sm.control_totals()
    return {k: after[k] - before[k] for k in after}


class TestTheBell:
    """The binding alone: the generation is what keeps a ring between a look
    at the queues and the wait from being lost."""

    def test_a_ring_before_the_wait_returns_it_at_once(self):
        bell = native_lib.control_bell()
        seen = bell.wait(0, 0)
        bell.ring()  # the push the loop's empty drain did not see
        t0 = time.perf_counter()
        now = bell.wait(seen, 5000)
        assert time.perf_counter() - t0 < 0.5
        assert now != seen

    def test_a_wait_on_the_current_generation_times_out(self):
        bell = native_lib.control_bell()
        bell.ring()
        seen = bell.wait(0, 0)
        t0 = time.perf_counter()
        assert bell.wait(seen, 60) == seen
        assert 0.05 <= time.perf_counter() - t0 < 2.0

    def test_a_ring_from_another_thread_ends_a_wait(self):
        bell = native_lib.control_bell()
        seen = bell.wait(0, 0)
        ringer = threading.Timer(0.05, bell.ring)
        ringer.start()
        try:
            t0 = time.perf_counter()
            now = bell.wait(seen, 5000)
            took = time.perf_counter() - t0
        finally:
            ringer.join(timeout=5)
        assert now == seen + 1
        assert took < 2.0

    def test_every_ring_moves_the_generation(self):
        bell = native_lib.control_bell()
        first = bell.wait(0, 0)
        for _ in range(5):
            bell.ring()
        assert bell.wait(first, 0) == first + 5

    def test_rings_from_many_threads_are_neither_lost_nor_slept_through(self):
        import os
        import sys

        bell = native_lib.control_bell()
        first = bell.wait(0, 0)
        ringers, each = 2 * (os.cpu_count() or 4), 500
        total = first + ringers * each
        slept = []  # waits that ran to their time-out though a ring followed

        def waiter():
            seen = first
            while seen != total:
                now = bell.wait(seen, 2000)
                if now == seen:
                    slept.append(seen)
                    return
                seen = now

        def ringer():
            for _ in range(each):
                bell.ring()

        was = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=waiter, daemon=True)]
            threads += [threading.Thread(target=ringer, daemon=True)
                        for _ in range(ringers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
        finally:
            sys.setswitchinterval(was)
        assert not any(t.is_alive() for t in threads)
        assert slept == []
        assert bell.wait(first, 0) == total  # every ring moved it by one

    def test_a_data_frame_alone_does_not_ring_it(self):
        # the door's own cv is notified by every data frame, 98,000 times a
        # second in a busy cell; the bell only by a push to the control
        # queue (here: the open event and the PING, not the batch frame)
        import numpy as np

        bell = native_lib.control_bell()
        door = native_lib.Frontdoor(port=0)
        door.set_bell(bell)
        sock = _connect(door.port)
        try:
            sock.sendall(P.encode_request(P.Ping(1)))
            deadline = time.monotonic() + 2.0
            while bell.wait(0, 0) < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            seen = bell.wait(0, 0)
            assert seen == 2  # the open event, the frame
            before = door.stats()["requests_in"]
            sock.sendall(P.encode_batch_request(7, np.full(20, 2, np.int64)))
            deadline = time.monotonic() + 2.0
            while (door.stats()["requests_in"] == before
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            assert door.stats()["requests_in"] == before + 20
            assert bell.wait(seen, 50) == seen
        finally:
            sock.close()
            door.stop()

    def test_a_zero_timeout_only_reads(self):
        bell = native_lib.control_bell()
        seen = bell.wait(0, 0)
        t0 = time.perf_counter()
        assert bell.wait(seen, 0) == seen
        assert time.perf_counter() - t0 < 0.5


class TestTheControlThreadSleepsOnIt:
    NAMES = ("control_wakeups_total", "control_idle_wakeups_total")

    def test_a_frame_to_an_idle_server_is_handled_at_once(self, server):
        # the bell, not the 100 ms time-out, wakes the thread: a PING sent
        # at a random moment of the thread's sleep comes back inside 20 ms
        sock = _connect(server.port)
        try:
            _ping_s(sock, 1)  # the open event and the first handshake
            took = []
            for xid in range(2, 17):
                _asleep()
                took.append(_ping_s(sock, xid))
        finally:
            sock.close()
        assert _mostly_prompt(took), sorted(took)

    def test_back_to_back_frames_lose_no_wakeup(self, server):
        # each reply triggers the next frame, so many arrive just as the
        # thread goes from an empty drain to its wait: a lost ring would
        # cost that frame the rest of the 100 ms time-out
        sock = _connect(server.port)
        try:
            took = [_ping_s(sock, xid) for xid in range(1, 301)]
        finally:
            sock.close()
        assert sum(t >= 0.05 for t in took) <= 6, sorted(took)[-8:]

    def test_an_idle_second_wakes_it_only_for_its_timeouts(self, server):
        _asleep()
        got = _idle_wakeups_in(1.0)
        assert got["control_idle_wakeups_total"] <= 15, got
        assert got["control_wakeups_total"] <= 15, got
        assert got["control_wakeups_total"] >= 5, got  # it does look at _stop

    def test_a_frame_is_a_wakeup_that_is_not_idle(self, server):
        sock = _connect(server.port)
        try:
            _ping_s(sock, 1)
            _asleep()
            sm = server_metrics()
            before = sm.control_totals()
            for xid in range(2, 12):
                _asleep()
                _ping_s(sock, xid)
            # the thread answers first and counts after: give the last
            # frame's count a moment
            deadline = time.monotonic() + 2.0
            while True:
                after = sm.control_totals()
                busy = [after[k] - before[k] for k in self.NAMES]
                if busy[0] - busy[1] >= 10 or time.monotonic() > deadline:
                    break
                time.sleep(0.01)
        finally:
            sock.close()
        assert busy[0] - busy[1] >= 10, (before, after)

    def test_stop_rings_it(self, svc):
        took = []
        for _ in range(2):  # the better of two: a loaded machine's hiccup
            srv = _started(svc)
            assert len(_control_threads()) == 1
            _asleep()
            t0 = time.perf_counter()
            srv.stop()
            took.append(time.perf_counter() - t0)
            assert _control_threads() == []
            assert srv._bell is None
        assert min(took) < 0.5, took

    def test_one_thread_and_one_bell_a_server(self, server):
        assert len(_control_threads()) == 1
        assert isinstance(server._bell, native_lib.Bell)
        assert all(d._bell is server._bell for d in server._doors)

    def test_the_timeout_is_the_lanes_cadence(self):
        import inspect

        default = inspect.signature(
            native_lib.Frontdoor.wait_any_into).parameters["timeout_ms"]
        assert server_native._CONTROL_WAIT_MS == default.default == 100


class TestARingSettlesBeforeItWakes:
    """A report comes in the same send as the data frame behind it: handled
    in that very moment its ingest shares the GIL with that frame's intake,
    prep and launch, which cost the verdict 0.7 ms on the chip's host
    (PERF.md section 6, PR 51). The 2 ms poll, by coming late, had kept most
    of them apart, so a wait that a ring ends stays asleep 1 ms more, the
    poll's mean, in native code and off the GIL."""

    def test_a_wait_that_a_ring_ends_sleeps_on(self):
        bell = native_lib.control_bell()
        seen = bell.wait(0, 0)
        threading.Timer(0.02, bell.ring).start()
        t0 = time.perf_counter()
        assert bell.wait(seen, 5000, 60) == seen + 1
        assert 0.075 <= time.perf_counter() - t0 < 2.0

    def test_so_does_one_that_finds_it_rung_already(self):
        bell = native_lib.control_bell()
        seen = bell.wait(0, 0)
        bell.ring()
        t0 = time.perf_counter()
        assert bell.wait(seen, 5000, 60) == seen + 1
        assert 0.055 <= time.perf_counter() - t0 < 2.0

    def test_a_timeout_does_not(self):
        bell = native_lib.control_bell()
        seen = bell.wait(0, 0)
        t0 = time.perf_counter()
        assert bell.wait(seen, 40, 1500) == seen
        assert time.perf_counter() - t0 < 1.0

    def test_a_read_does_not(self):
        bell = native_lib.control_bell()
        seen = bell.wait(0, 0)
        bell.ring()
        t0 = time.perf_counter()
        assert bell.wait(seen, 0, 1500) == seen + 1
        assert time.perf_counter() - t0 < 1.0

    def test_rings_while_it_settles_are_in_what_it_returns(self):
        bell = native_lib.control_bell()
        seen = bell.wait(0, 0)
        bell.ring()
        threading.Timer(0.02, bell.ring).start()
        assert bell.wait(seen, 5000, 100) == seen + 2

    def test_a_frame_to_a_sleeping_thread_waits_it(self, server):
        sock = _connect(server.port)
        try:
            _ping_s(sock, 1)
            took = []
            for xid in range(2, 17):
                _asleep()
                took.append(_ping_s(sock, xid))
        finally:
            sock.close()
        assert statistics.median(took) >= server_native._CONTROL_SETTLE_MS * 0.9e-3
        assert _mostly_prompt(took), sorted(took)  # a millisecond, not more

    def test_it_is_the_polls_mean(self):
        assert server_native._CONTROL_SETTLE_MS == 2 / 2


class TestEveryDoorRingsIt:
    def test_the_second_tcp_door_wakes_the_same_thread(self, svc):
        srv = _started(svc, intake_shards=2)
        socks = []
        try:
            assert len(srv._doors) == 2
            assert len(_control_threads()) == 1
            # the kernel deals connections over the two listeners; open
            # until each door has read some bytes, PING on every one
            for xid in range(1, 65):
                sock = _connect(srv.port)
                socks.append(sock)
                _asleep()
                took = _ping_s(sock, xid)
                reached = [d.stats()["bytes_in"] > 0 for d in srv._doors]
                if all(reached) and xid >= 8:
                    break
            assert all(reached), reached
            # whichever door a connection landed on, its frames come back
            # at once: both doors ring the one bell
            took = []
            for i, sock in enumerate(socks):
                _asleep()
                took.append(_ping_s(sock, 1000 + i))
            assert _mostly_prompt(took), took
        finally:
            for sock in socks:
                sock.close()
            srv.stop()

    def test_the_shm_door_wakes_the_same_thread(self, svc, tmp_path):
        if not native_lib.shm_available():
            pytest.skip("native shm door not built")
        srv = _started(svc, shm_dir=str(tmp_path))
        ring = None
        try:
            assert len(srv._doors) == 2
            assert len(_control_threads()) == 1
            ring = native_lib.ShmRingClient(str(tmp_path), n_slots=8)
            took = []
            for xid in range(1, 17):
                _asleep()
                t0 = time.perf_counter()
                assert ring.send_frame(P.encode_request(P.Ping(xid)),
                                       timeout_ms=2000)
                payload = ring.recv_payload(timeout_ms=3000)
                took.append(time.perf_counter() - t0)
                assert payload is not None
                assert int.from_bytes(payload[:4], "big") == xid
            # the first waits for the poller's directory scan to attach the
            # segment (up to 200 ms): the door's, not the bell's
            assert _mostly_prompt(took[1:]), took
        finally:
            if ring is not None:
                ring.close()
            srv.stop()


class TestALibraryWithoutTheBell:
    @pytest.fixture()
    def no_bell(self, monkeypatch):
        lib = native_lib.load()
        monkeypatch.setattr(lib, "_sn_has_bell", False, raising=False)

    def test_the_binding_returns_none(self, no_bell):
        assert native_lib.control_bell() is None

    def test_the_thread_polls_every_2_ms_and_still_serves(self, svc, no_bell):
        srv = _started(svc)
        try:
            assert srv._bell is None
            assert len(_control_threads()) == 1
            sock = _connect(srv.port)
            try:
                took = [_ping_s(sock, xid) for xid in range(1, 21)]
            finally:
                sock.close()
            assert _mostly_prompt(took), took
            got = _idle_wakeups_in(1.0)
            # about 500 on an idle machine; far past the bell's 10
            assert got["control_idle_wakeups_total"] >= 50, got
        finally:
            srv.stop()
        assert _control_threads() == []


class TestTheCounters:
    NAMES = ("control_wakeups_total", "control_idle_wakeups_total")

    @pytest.mark.parametrize("name", NAMES)
    def test_in_the_stage_snapshot(self, name):
        assert server_metrics().stage_snapshot()[name] >= 0

    @pytest.mark.parametrize("name,key", zip(
        NAMES, ("controlWakeupsTotal", "controlIdleWakeupsTotal")))
    def test_in_the_json_snapshot(self, name, key):
        sm = server_metrics()
        assert sm.snapshot()[key] == sm.control_totals()[name]

    @pytest.mark.parametrize("name", NAMES)
    def test_on_the_metrics_page(self, name):
        text = server_metrics().render()
        assert f"# TYPE sentinel_server_{name} counter" in text

    def test_idle_is_a_subset_and_reset_zeroes_both(self):
        from sentinel_tpu.metrics.server import ServerMetrics

        sm = ServerMetrics()
        sm.count_control_wakeup(idle=True)
        sm.count_control_wakeup(idle=False)
        sm.count_control_wakeup(idle=True)
        assert sm.control_totals() == {
            "control_wakeups_total": 3, "control_idle_wakeups_total": 2}
        sm.reset()
        assert sm.control_totals() == dict.fromkeys(self.NAMES, 0)
