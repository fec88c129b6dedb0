"""Byte-level fuzz of the native front door's frame decoder.

The robustness contract mirrored here is the reference's
``LengthFieldBasedFrameDecoder(1024,0,2,0,2)`` + request-decoder stack
(``NettyTransportServer.java:80``): arbitrary bytes on the wire may close
THAT connection but must never crash the server, corrupt another
connection's responses, or wedge the arena.

Importable (``run_fuzz``) so the pytest case and the ASan harness share one
corpus strategy:

- pure random garbage (runt frames, bad types, random lengths);
- MUTATED valid frames (bit flips in length/type/n/rows — the hardest class,
  since most of the frame still parses), BATCH_FLOW and, codec rev 8,
  BATCH_PARAM_FLOW (type 27: ``n:u16 k:u8`` and rows of ``13 + 8k`` bytes,
  so a flipped ``k`` moves every row boundary) and, codec rev 9,
  BATCH_CONCURRENT_ACQUIRE / _RELEASE (types 28 and 29, whose body must be
  exactly its rows: one byte more or less closes the connection; an
  acquire frame is answered up to ``MAX_ACQUIRE_ROWS`` rows, the most whose
  17-byte reply rows fit a frame, and closes the connection past it:
  ``acquire_bound_case``), and the reference client's single frames that
  are data plane since PR 45: PARAM_FLOW (type 2: FLOW's body, ``n:u8``,
  then ``n`` hashes, so a flipped ``n`` declares values the body lacks and
  closes the connection) and CONCURRENT_ACQUIRE / _RELEASE (types 3 and 4,
  FLOW's body);
- TRUNCATED valid frames followed by socket close mid-frame;
- oversize declared n vs actual payload;
- valid frames delivered 1–3 bytes at a time interleaved with garbage
  connections (partial-parse state machine);
- arena-boundary pressure: a tiny-cap server parked mid-fuzz must resume.

After every connection's worth of fuzz, a fresh VALID client performs a
round trip — the liveness oracle. Run standalone (ASan build)::

    make -C native asan-check
"""

from __future__ import annotations

import os
import random
import socket
import struct
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _valid_batch_frame(xid: int, n: int) -> bytes:
    rows = b"".join(
        struct.pack(">qiB", random.randrange(0, 64), 1, 0) for _ in range(n)
    )
    payload = struct.pack(">iB", xid, 5) + struct.pack(">H", n) + rows
    return struct.pack(">H", len(payload)) + payload


def _valid_param_frame(xid: int, n: int, k: int) -> bytes:
    """One BATCH_PARAM_FLOW frame: ``n`` requests of ``k`` value hashes."""
    rows = b"".join(
        struct.pack(">qiB", random.randrange(0, 64), 1, 0)
        + struct.pack(f">{k}q", *(random.randrange(1 << 40) for _ in range(k)))
        for _ in range(n)
    )
    payload = struct.pack(">iB", xid, 27) + struct.pack(">HB", n, k) + rows
    return struct.pack(">H", len(payload)) + payload


def _valid_acquire_frame(xid: int, n: int) -> bytes:
    """One BATCH_CONCURRENT_ACQUIRE frame: BATCH_FLOW's bytes, type 28."""
    f = bytearray(_valid_batch_frame(xid, n))
    f[6] = 28
    return bytes(f)


def _valid_release_frame(xid: int, n: int) -> bytes:
    """One BATCH_CONCURRENT_RELEASE frame: ``n`` token ids."""
    payload = struct.pack(">iBH", xid, 29, n) + struct.pack(
        f">{n}q", *(random.randrange(1 << 40) for _ in range(n)))
    return struct.pack(">H", len(payload)) + payload


def _valid_single_frame(xid: int, mtype: int, k: int = 0) -> bytes:
    """One single frame of FLOW's body under ``mtype`` (1, 3 or 4), or a
    PARAM_FLOW frame (2) with ``k`` value hashes behind ``n:u8``."""
    payload = struct.pack(">iB", xid, mtype) + struct.pack(
        ">qiB", random.randrange(0, 64), 1, 0)
    if mtype == 2:
        payload += struct.pack(f">B{k}q", k, *(random.randrange(1 << 40)
                                               for _ in range(k)))
    return struct.pack(">H", len(payload)) + payload


def _valid_frame(xid: int, n: int, rng: random.Random) -> bytes:
    """A valid frame of any data-plane kind: a batch frame of ``n`` rows or
    a single frame."""
    kind = rng.randrange(6)
    if kind == 4:
        return _valid_single_frame(xid, 2, rng.randrange(0, 6))
    if kind == 5:
        return _valid_single_frame(xid, rng.choice((1, 3, 4)))
    if kind == 0:
        return _valid_param_frame(xid, n, rng.randrange(1, 5))
    if kind == 1:
        return _valid_acquire_frame(xid, n)
    if kind == 2:
        return _valid_release_frame(xid, n)
    return _valid_batch_frame(xid, n)


# the most acquire rows whose reply (17 B a row) fits a 65535-byte frame;
# the request's 13-byte rows would hold 5040 (protocol.MAX_ACQUIRE_PER_FRAME)
MAX_ACQUIRE_ROWS = (65535 - 7) // 17
MAX_REQUEST_ROWS = (65535 - 7) // 13


def acquire_bound_case(port: int, timeout: float = 10.0) -> bool:
    """The acquire frame's row bound: ``MAX_ACQUIRE_ROWS`` rows are answered
    in one frame whose length prefix is whole; one row more, and the most
    the request's own rows allow, close the connection unanswered (a reply
    of their rows would wrap the u16 length and misframe the stream)."""
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as s:
        s.settimeout(timeout)
        s.sendall(_valid_acquire_frame(11, MAX_ACQUIRE_ROWS))
        want = 2 + 7 + MAX_ACQUIRE_ROWS * 17
        buf = b""
        while len(buf) < want:
            chunk = s.recv(1 << 16)
            if not chunk:
                return False
            buf += chunk
        if (len(buf) != want
                or struct.unpack(">HiBH", buf[:9])
                != (want - 2, 11, 28, MAX_ACQUIRE_ROWS)):
            return False
    for n in (MAX_ACQUIRE_ROWS + 1, MAX_REQUEST_ROWS):
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=timeout) as s:
            s.settimeout(timeout)
            s.sendall(_valid_acquire_frame(12, n))
            try:
                if s.recv(64) != b"":
                    return False
            except ConnectionResetError:
                pass  # closed with our bytes unread
    return True


def _valid_flow_frame(xid: int) -> bytes:
    payload = struct.pack(">iB", xid, 1) + struct.pack(">qiB", 1, 1, 0)
    return struct.pack(">H", len(payload)) + payload


def _mutate(frame: bytes, rng: random.Random) -> bytes:
    b = bytearray(frame)
    for _ in range(rng.randrange(1, 4)):
        i = rng.randrange(len(b))
        b[i] ^= 1 << rng.randrange(8)
    return bytes(b)


def _oracle_roundtrip(port: int, timeout: float = 5.0) -> bool:
    """One valid BATCH_FLOW, BATCH_PARAM_FLOW, BATCH_CONCURRENT_ACQUIRE and
    BATCH_CONCURRENT_RELEASE round trip on a fresh connection: four verdict
    rows each, under the request's type and in its row size; then one of
    each single frame (PARAM_FLOW with values and with none)."""
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as s:
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.settimeout(timeout)
        for xid, mtype, frame, row in (
            (7, 5, _valid_batch_frame(xid=7, n=4), 9),
            (8, 27, _valid_param_frame(xid=8, n=4, k=2), 9),
            (9, 28, _valid_acquire_frame(xid=9, n=4), 17),
            (10, 29, _valid_release_frame(xid=10, n=4), 1),
        ):
            s.sendall(frame)
            buf = b""
            while (len(buf) < 2
                   or len(buf) < 2 + struct.unpack(">H", buf[:2])[0]):
                chunk = s.recv(4096)
                if not chunk:
                    return False
                buf += chunk
            flen = struct.unpack(">H", buf[:2])[0]
            got = struct.unpack(">iBH", buf[2:9])
            if got != (xid, mtype, 4) or flen != 7 + 4 * row:
                return False
        # the single frames: one verdict each, FLOW's reply under the
        # request's type, an acquire's with its token id behind it
        for xid, mtype, k, size in ((11, 1, 0, 14), (12, 2, 3, 14),
                                    (13, 2, 0, 14), (14, 3, 0, 22),
                                    (15, 4, 0, 14)):
            s.sendall(_valid_single_frame(xid, mtype, k))
            buf = b""
            while len(buf) < 2 + size:
                chunk = s.recv(4096)
                if not chunk:
                    return False
                buf += chunk
            if struct.unpack(">HiB", buf[:7]) != (size, xid, mtype):
                return False
        return True
    return False


def _fuzz_one_conn(port: int, rng: random.Random) -> None:
    """One connection's worth of hostile bytes; server may close on us."""
    kind = rng.randrange(5)
    try:
        with socket.create_connection(("127.0.0.1", port), timeout=2) as s:
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            if kind == 0:  # pure garbage
                s.sendall(rng.randbytes(rng.randrange(1, 4096)))
            elif kind == 1:  # mutated valid frames
                for _ in range(rng.randrange(1, 8)):
                    f = _valid_frame(rng.randrange(1, 1 << 30),
                                     rng.randrange(0, 32), rng)
                    s.sendall(_mutate(f, rng))
            elif kind == 2:  # truncated frame, close mid-parse
                f = _valid_frame(1, rng.randrange(1, 64), rng)
                s.sendall(f[: rng.randrange(1, len(f))])
            elif kind == 3 and rng.randrange(8) == 0:
                # whole acquire rows, more of them than a reply can answer
                s.sendall(_valid_acquire_frame(1, rng.randrange(
                    MAX_ACQUIRE_ROWS + 1, MAX_REQUEST_ROWS + 1)))
            elif kind == 3:  # oversize declared n vs actual rows
                n_claim = rng.randrange(64, 5000)
                head = (struct.pack(">iBH", 1, rng.choice((5, 28, 29)),
                                    n_claim)
                        if rng.randrange(2) else
                        struct.pack(">iBHB", 1, 27, n_claim,
                                    rng.randrange(0, 256)))
                payload = head + rng.randbytes(rng.randrange(0, 64))
                s.sendall(struct.pack(">H", len(payload)) + payload)
            else:  # drip-feed a valid frame in tiny chunks, then garbage
                f = (_valid_batch_frame(3, 8) + _valid_flow_frame(4)
                     + _valid_param_frame(5, 8, 3)
                     + _valid_release_frame(6, 5)
                     + _valid_acquire_frame(7, 8)
                     + _valid_single_frame(8, 2, 2)
                     + _valid_single_frame(9, 3)
                     + _valid_single_frame(10, 4))
                i = 0
                while i < len(f):
                    step = rng.randrange(1, 4)
                    s.sendall(f[i : i + step])
                    i += step
                # valid frames' responses may arrive; drain nonblocking
                s.settimeout(0.2)
                try:
                    s.recv(4096)
                except (socket.timeout, OSError):
                    pass
                s.sendall(rng.randbytes(rng.randrange(1, 128)))
            # give the server a beat to process / close
            s.settimeout(0.2)
            try:
                s.recv(4096)
            except (socket.timeout, OSError):
                pass
    except OSError:
        pass  # connection refused/reset mid-fuzz is fine; liveness is checked


def run_fuzz(iters: int = 200, seed: int = 0, arena_cap: int = 65536,
             oracle_every: int = 10) -> dict:
    """Stand up a native server and fuzz it; returns stats, raises on a
    liveness failure (the crash signal when run under ASan)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from sentinel_tpu.cluster.server_native import (
        NativeTokenServer,
        native_available,
    )
    from sentinel_tpu.cluster.concurrent import ConcurrentFlowRule
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
    from sentinel_tpu.engine.rules import ThresholdMode

    if not native_available():
        raise RuntimeError("native library not built")
    cfg = EngineConfig(max_flows=64, max_namespaces=4, batch_size=256)
    svc = DefaultTokenService(cfg)
    svc.load_rules([
        ClusterFlowRule(flow_id=i, count=1e9, mode=ThresholdMode.GLOBAL)
        for i in range(64)
    ])
    svc.load_concurrent_rules(
        [ConcurrentFlowRule(i, 4, resource_timeout_ms=200)
         for i in range(64)])
    server = NativeTokenServer(svc, port=0, idle_ttl_s=None,
                               arena_cap=arena_cap)
    server.start()
    rng = random.Random(seed)
    checks = 0
    try:
        assert _oracle_roundtrip(server.port), "server dead before fuzz"
        assert acquire_bound_case(server.port), "acquire row bound"
        for i in range(iters):
            _fuzz_one_conn(server.port, rng)
            if (i + 1) % oracle_every == 0:
                assert _oracle_roundtrip(server.port), (
                    f"liveness oracle failed after fuzz iteration {i} "
                    f"(seed {seed})"
                )
                checks += 1
        assert acquire_bound_case(server.port), "acquire row bound"
        assert _oracle_roundtrip(server.port), "server dead after fuzz"
        stats = server.stats()
    finally:
        server.stop()
        svc.close()
    return {"iters": iters, "oracle_checks": checks + 2, "stats": stats}


def run_fuzz_raw(iters: int = 300, seed: int = 0,
                 arena_cap: int = 65536, oracle_every: int = 10) -> dict:
    """Same corpus against a bare ``Frontdoor`` with a constant-verdict
    dispatch loop — no jit ever executes. This is the ASan harness mode:
    ASan's ``__cxa_throw`` interceptor is incompatible with jaxlib's
    nanobind exception machinery, so the sanitized run must keep the
    entire jax execution path cold (imports are fine; jit calls are not).
    It is also the purest decoder fuzz: every byte the corpus can reach is
    C++."""
    import threading

    import numpy as np

    from sentinel_tpu.native.lib import Frontdoor, available

    if not available():
        raise RuntimeError("native library not built")
    door = Frontdoor("127.0.0.1", 0, arena_cap=max(arena_cap, 1))
    stop = threading.Event()

    cap = door.arena_cap
    block = dict(
        ids=np.empty(cap, np.int64), counts=np.empty(cap, np.int32),
        prios=np.empty(cap, np.uint8), hashes=np.empty(cap, np.int64),
        **{k: np.empty(cap, np.uint8 if k == "f_type" else np.int32)
           for k in ("f_fd", "f_gen", "f_xid", "f_n", "f_type")},
    )

    def dispatch():
        # flow, param and concurrency pulls alike: every row GRANTED (a
        # release row's status byte is then 0 too; the oracle reads sizes)
        while not stop.is_set():
            got = door.wait_any_into(block, timeout_ms=50)
            if got is None:
                continue
            n, k, _nv = got
            frames = tuple(block[f][:k].copy() for f in (
                "f_fd", "f_gen", "f_xid", "f_n", "f_type"))
            door.submit(frames, np.zeros(n, np.int8),
                        np.zeros(n, np.int32), np.zeros(n, np.int32))

    def control():
        while not stop.is_set():
            item = door.next_control()
            if item is None:
                time.sleep(0.002)
                continue
            kind, fd, gen, payload = item
            # a PARAM_FLOW frame with no value is the control plane's: OK
            if (kind == door.CTRL_FRAME and len(payload) >= 5
                    and payload[4] == 2):
                body = payload[:5] + struct.pack(">bii", 0, 0, 0)
                door.send(fd, gen, struct.pack(">H", len(body)) + body)

    threads = [threading.Thread(target=dispatch, daemon=True),
               threading.Thread(target=control, daemon=True)]
    for t in threads:
        t.start()
    rng = random.Random(seed)
    checks = 0
    try:
        assert _oracle_roundtrip(door.port), "front door dead before fuzz"
        assert acquire_bound_case(door.port), "acquire row bound"
        for i in range(iters):
            _fuzz_one_conn(door.port, rng)
            if (i + 1) % oracle_every == 0:
                assert _oracle_roundtrip(door.port), (
                    f"liveness oracle failed after fuzz iteration {i} "
                    f"(seed {seed})"
                )
                checks += 1
        assert acquire_bound_case(door.port), "acquire row bound"
        assert _oracle_roundtrip(door.port), "front door dead after fuzz"
        stats = door.stats()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
        door.stop()
    return {"iters": iters, "oracle_checks": checks + 2, "stats": stats}


if __name__ == "__main__":
    iters = int(sys.argv[1]) if len(sys.argv) > 1 else 500
    t0 = time.time()
    seed = int(os.environ.get("FUZZ_SEED", "0"))
    if os.environ.get("FUZZ_RAW"):
        out = run_fuzz_raw(iters=iters, seed=seed)
    else:
        out = run_fuzz(iters=iters, seed=seed)
    print(f"fuzz ok: {out['iters']} hostile conns, "
          f"{out['oracle_checks']} liveness checks, {time.time()-t0:.1f}s")
