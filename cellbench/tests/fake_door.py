"""A token server made of the plain reference behind a plain socket: the
control's way into the program's place, and a door that can misbehave on
purpose (stall, shed, drop a connection) for the accounting tests."""

from __future__ import annotations

import socket
import struct
import threading
import time

import numpy as np

from cellbench import deploy, wire


# the semaphore fixture's frames (``extra/families/semaphore.py`` writes its
# own copy): ACQUIRE is BATCH_FLOW's request under another type, answered
# with a token id a row; RELEASE is ``n:u16`` then ``token_id:i64`` a row
ACQUIRE, RELEASE = 40, 41
ACQ_ROW = np.dtype([("status", "i1"), ("remaining", ">i4"), ("wait_ms", ">i4"),
                    ("token_id", ">i8")])


class FakeDoor:
    """Answers BATCH_FLOW and FLOW frames from ``decide(ids, acq) ->
    (status, remaining, wait_ms)``. ``stall=(after_frames, seconds)`` sleeps
    once; ``shed_from`` answers OVERLOAD from that frame on for ``shed_n``
    frames; ``drop_at`` closes the connection on receiving that frame.

    An ACQUIRE frame is answered as a BATCH_FLOW frame, each row that passed
    with a token id no other row gets: ``issued[id]`` is ``(the frame's xid,
    when its reply was handed to the socket)``. A RELEASE frame is not
    answered: ``released`` holds ``(id, when it came)`` for every id of
    every RELEASE frame, known or not, in the order they came."""

    def __init__(self, decide, stall=None, shed_from=None, shed_n=0,
                 drop_at=None):
        self.decide = decide
        self.stall, self.shed_from, self.shed_n = stall, shed_from, shed_n
        self.drop_at = drop_at
        self.frames = 0
        self.lock = threading.Lock()
        self.issued, self.released = {}, []
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(16)
        self.port = self.sock.getsockname()[1]
        self.stop = False
        self.thread = threading.Thread(target=self._accept, daemon=True)
        self.thread.start()

    def close(self) -> None:
        self.stop = True
        try:
            self.sock.close()
        except OSError:
            pass

    def _accept(self) -> None:
        while not self.stop:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _answer(self, ids, acq):
        with self.lock:
            self.frames += 1
            k = self.frames
            if self.stall and k == self.stall[0]:
                time.sleep(self.stall[1])
            if self.drop_at is not None and k == self.drop_at:
                return None
            if (self.shed_from is not None
                    and self.shed_from <= k < self.shed_from + self.shed_n):
                n = len(ids)
                return (np.full(n, deploy.OVERLOAD, np.int8),
                        np.zeros(n, np.int32), np.full(n, 5, np.int32))
            return self.decide(ids, acq)

    def _issue(self, xid: int, status) -> np.ndarray:
        """A fresh id for every row that passed, 0 for the others."""
        ids = np.zeros(len(status), np.int64)
        ok = np.flatnonzero(np.asarray(status) == deploy.OK)
        now = time.monotonic()
        with self.lock:
            first = 1 + len(self.issued)
            ids[ok] = first + np.arange(len(ok))
            self.issued.update((i, (xid, now)) for i in ids[ok].tolist())
        return ids

    def _serve(self, conn) -> None:
        buf = bytearray()
        try:
            while not self.stop:
                data = conn.recv(1 << 16)
                if not data:
                    return
                buf += data
                while len(buf) >= 2:
                    flen = struct.unpack_from(">H", buf, 0)[0]
                    if len(buf) < 2 + flen:
                        break
                    xid, mtype = struct.unpack_from(">ib", buf, 2)
                    if mtype == RELEASE:
                        n = struct.unpack_from(">H", buf, 7)[0]
                        ids = np.frombuffer(bytes(buf[9:9 + 8 * n]), ">i8")
                        del buf[:2 + flen]
                        now = time.monotonic()
                        with self.lock:
                            self.released += [(i, now) for i in ids.tolist()]
                        continue
                    if mtype in (wire.BATCH_FLOW, ACQUIRE):
                        n = struct.unpack_from(">H", buf, 7)[0]
                        rows = np.frombuffer(bytes(buf[9:9 + 13 * n]),
                                             wire.REQ_ROW)
                    elif mtype == wire.FLOW:
                        n = 1
                        rows = np.frombuffer(bytes(buf[7:20]), wire.REQ_ROW)
                    else:  # a frame that is not answered (a report)
                        del buf[:2 + flen]
                        continue
                    del buf[:2 + flen]
                    out = self._answer(rows["flow_id"].astype(np.int64),
                                       rows["count"].astype(np.int32))
                    if out is None:
                        conn.close()
                        return
                    status, remaining, wait = out
                    rsp = np.empty(n, ACQ_ROW if mtype == ACQUIRE
                                   else wire.RSP_ROW)
                    rsp["status"], rsp["remaining"] = status, remaining
                    rsp["wait_ms"] = wait
                    if mtype == ACQUIRE:
                        rsp["token_id"] = self._issue(xid, rsp["status"])
                    if mtype == wire.FLOW:
                        head = struct.pack(">Hib", 14, xid, wire.FLOW)
                    else:
                        head = struct.pack(">HibH", 7 + rsp.itemsize * n, xid,
                                           mtype, n)
                    conn.sendall(head + rsp.tobytes())
        except OSError:
            return


def reference_decider(ref, t_ms: int = 50_000):
    """``decide`` for a FakeDoor: the reference, all rows at one instant."""
    def decide(ids, acq):
        status, wait = ref.decide_frame(t_ms, ids, acq)
        n = len(ids)
        # an unmetered pass reports the tokens left, which is never 0
        return (np.asarray(status, np.int8), np.full(n, 7, np.int32),
                np.asarray(wait, np.int32))
    return decide
