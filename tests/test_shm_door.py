"""Shm ring front door: torn/hostile-writer fuzz and client-death reclaim.

The ring's publish protocol (payload memcpy → len word → release-store of
the tail) means a client killed or parked mid-slot-write never publishes
the slot — the server must never observe a torn frame, at any ring index
including the wrap boundary. Hostile publishes (bogus len word, garbage
payload) must resolve like TCP garbage: segment dropped or frame answered,
never a wedged poller. A SIGKILL'd client's segment must be reclaimed by
the pid sweep, and the door must keep serving fresh clients through all
of it.
"""

import os
import signal
import struct
import subprocess
import sys
import time

import numpy as np
import pytest

from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster.server_native import NativeTokenServer
from sentinel_tpu.cluster.shm_client import ShmTokenClient
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig, TokenStatus
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.native.lib import ShmRingClient, shm_available

pytestmark = pytest.mark.skipif(
    not shm_available(), reason="native shm door not built"
)

G = ThresholdMode.GLOBAL
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=256)

N_SLOTS = 8  # small ring so tests cross the wrap boundary quickly


@pytest.fixture(scope="module")
def shm_server(tmp_path_factory):
    svc = DefaultTokenService(CFG)
    svc.load_rules([
        ClusterFlowRule(flow_id=1, count=1e9, mode=G),
    ])
    shm_dir = str(tmp_path_factory.mktemp("shm-door"))
    server = NativeTokenServer(svc, port=0, idle_ttl_s=None, shm_dir=shm_dir)
    server.start()
    yield server, shm_dir
    server.stop()


def _segments(server) -> int:
    return int(server.stats().get("shm_segments", 0))


def _wait_segments(server, want: int, timeout_s: float = 3.0) -> int:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        n = _segments(server)
        if n == want:
            return n
        time.sleep(0.02)
    return _segments(server)


def _assert_still_serving(shm_dir):
    c = ShmTokenClient(shm_dir, timeout_ms=3000)
    try:
        assert c.ping()
        out = c.request_batch_arrays(np.full(4, 1, np.int64))
        assert out is not None and (out[0] == int(TokenStatus.OK)).all()
    finally:
        c.close()


def _roundtrip(ring: ShmRingClient, xid: int) -> None:
    """One 3-row batch through the raw ring; asserts xid exactness and
    OK verdicts — the probe that a torn stage changed nothing."""
    frame = P.encode_batch_request(
        xid, np.full(3, 1, np.int64),
        np.full(3, 1, np.int32), np.zeros(3, np.uint8),
    )
    assert ring.send_frame(frame, timeout_ms=2000)
    payload = ring.recv_payload(timeout_ms=3000)
    assert payload is not None, f"no response for xid {xid}"
    got = struct.unpack(">i", payload[:4])[0]
    assert got == xid, f"xid mismatch: sent {xid}, got {got}"
    n = struct.unpack(">H", payload[5:7])[0]
    assert n == 3
    status = np.frombuffer(payload[7:7 + 9 * 3], np.uint8)[0::9].view(np.int8)
    assert (status == int(TokenStatus.OK)).all()


class TestTornWriter:
    def test_torn_stage_never_read_at_every_boundary(self, shm_server):
        """Stages 0 (full payload + len staged, unpublished) and 1 (half
        payload, no len) at EVERY ring index across two full wraps: the
        server must never consume the staged garbage, and the valid frame
        that overwrites the slot next must round-trip with its exact
        xid."""
        server, shm_dir = shm_server
        ring = ShmRingClient(shm_dir, n_slots=N_SLOTS)
        try:
            garbage = bytes(range(256)) * 4
            for i in range(2 * N_SLOTS + 1):  # crosses the wrap twice
                assert ring.fuzz(garbage, stage=0)
                assert ring.fuzz(garbage, stage=1)
                _roundtrip(ring, xid=100 + i)
            assert ring.alive()
        finally:
            ring.close()
        _assert_still_serving(shm_dir)

    def test_hostile_len_word_drops_segment(self, shm_server):
        """Stage 2 publishes a slot whose len word exceeds the slot
        capacity — the server must drop the whole segment (never read past
        the slot), and the poller must keep serving fresh segments."""
        server, shm_dir = shm_server
        ring = ShmRingClient(shm_dir, n_slots=N_SLOTS)
        try:
            assert ring.fuzz(b"x", stage=2)
            # the drop surfaces as ConnectionResetError on either side
            with pytest.raises(ConnectionResetError):
                for _ in range(100):  # bounded: drop lands within ~100ms
                    payload = ring.recv_payload(timeout_ms=50)
                    assert payload is None
            assert not ring.alive()
        finally:
            ring.close()
        assert _wait_segments(server, 0) == 0
        _assert_still_serving(shm_dir)

    def test_garbage_payload_flows_to_validation(self, shm_server):
        """Stage 3 publishes valid-length garbage — the same hostile bytes
        the TCP fuzz corpus throws. Whatever the verdict (answered, ignored
        or segment dropped), the poller must not wedge and the door must
        keep serving."""
        server, shm_dir = shm_server
        for blob in (
            b"\xff" * 64,                       # bogus type byte
            b"\x00" * 4,                        # runt: below header size
            struct.pack(">ib", 5, 5) + b"\xff\xff",  # lying row count
            bytes(range(200)),                  # random-ish structure
        ):
            ring = ShmRingClient(shm_dir, n_slots=N_SLOTS)
            try:
                assert ring.fuzz(blob, stage=3)
                try:
                    ring.recv_payload(timeout_ms=200)
                except ConnectionResetError:
                    pass  # dropped like a TCP parse violation — fine
            finally:
                ring.close()
            _assert_still_serving(shm_dir)
        assert _wait_segments(server, 0) == 0

    def test_ring_full_backpressure_not_death(self, shm_server):
        """A burst the client doesn't drain is backpressure, never death:
        every published request is either answered or dropped into the
        ``ring_full`` counter (the response ring's bounded-wait overflow),
        the segment survives, and the next round-trip works."""
        server, shm_dir = shm_server
        full_before = int(server.stats().get("shm_ring_full", 0))
        ring = ShmRingClient(shm_dir, n_slots=N_SLOTS)
        try:
            frame = P.encode_batch_request(
                7, np.full(1, 1, np.int64),
                np.full(1, 1, np.int32), np.zeros(1, np.uint8),
            )
            sent = 0
            for _ in range(4 * N_SLOTS):  # no recv: response ring backs up
                if ring.send_frame(frame, timeout_ms=200):
                    sent += 1
            assert sent >= N_SLOTS  # the request ring drained at least once
            got = 0
            while ring.recv_payload(timeout_ms=500) is not None:
                got += 1
            dropped = int(server.stats().get("shm_ring_full", 0)) - full_before
            assert got + dropped == sent, (
                f"answered {got} + dropped {dropped} != published {sent}"
            )
            assert ring.alive()
            _roundtrip(ring, xid=4242)  # backpressure never killed the lane
        finally:
            ring.close()


_KILL_CHILD = r"""
import os, signal, sys
import numpy as np
from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.native.lib import ShmRingClient

shm_dir, advance, stage = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
ring = ShmRingClient(shm_dir, n_slots=8)
frame = P.encode_batch_request(
    1, np.full(1, 1, np.int64), np.full(1, 1, np.int32),
    np.zeros(1, np.uint8),
)
for i in range(advance):  # park the write cursor at the target ring index
    assert ring.send_frame(frame, timeout_ms=2000)
    assert ring.recv_payload(timeout_ms=3000) is not None
assert ring.fuzz(b"torn" * 64, stage)  # mid-slot-write state, unpublished
sys.stdout.write("READY\n")
sys.stdout.flush()
os.kill(os.getpid(), signal.SIGSTOP)  # park until the parent SIGKILLs us
"""


class TestClientDeath:
    @pytest.mark.parametrize(
        "advance,stage",
        [(0, 0), (7, 1), (8, 0)],  # ring start, last index, wrap boundary
    )
    def test_sigkill_mid_write_reclaims_segment(
        self, shm_server, advance, stage
    ):
        """A client SIGKILL'd parked mid-slot-write (torn stage, never
        published): the pid sweep must reclaim its segment, the torn bytes
        must never surface as a frame, and the door keeps serving."""
        server, shm_dir = shm_server
        before = server.stats()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-c", _KILL_CHILD, shm_dir,
             str(advance), str(stage)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            text=True,
        )
        try:
            line = proc.stdout.readline()
            assert line.strip() == "READY", (
                f"child failed: {proc.stderr.read()}"
            )
            # a client that has sent nothing yet (advance 0) is known to
            # the door only from its next directory sweep: wait for that
            deadline = time.monotonic() + 3.0
            while _segments(server) < 1 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert _segments(server) >= 1
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
        # pid sweep (500ms cadence) reclaims the orphan segment
        assert _wait_segments(server, 0) == 0
        after = server.stats()
        # the torn slot was never consumed as a frame: frames_in grew only
        # by what the child's valid advance sends published (plus the
        # handshake-free raw sends have no pings). Each advance iteration
        # is exactly one frame.
        torn_consumed = (
            after["frames_in"] - before["frames_in"] - advance
        )
        assert torn_consumed <= 0, (
            f"server consumed {torn_consumed} unpublished torn frame(s)"
        )
        _assert_still_serving(shm_dir)

    def test_segment_files_unlinked_after_death(self, shm_server):
        """After reclaim, no orphan seg-*.ring files linger in the dir
        (the unlink half of the liveness contract)."""
        server, shm_dir = shm_server
        _wait_segments(server, 0)
        rings = [f for f in os.listdir(shm_dir) if f.endswith(".ring")]
        assert rings == []
