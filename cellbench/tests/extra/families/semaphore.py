"""The semaphore shape, as a fixture: a flow table (``flow.py``'s deployment
and rows) whose admitted rows each hold a token that the client gives back.
It proves the session of ``families/__init__.py`` (frames whose bytes depend
on replies the same generator has read) against ``tests/fake_door.py``; it
is not a benchmark configuration, and no server of the program speaks these
frames. The next ``model_config`` PR grows the like of it into
``cellbench/families/`` for cluster concurrency limiting, with the program's
own batch frames.

Wire (this fixture's own, in the reference's framing): ``ACQUIRE`` (type 40)
is BATCH_FLOW's request under another type byte; its reply's rows are FLOW's
response with ``token_id:i64`` behind it, 0 where the row was not admitted.
``RELEASE`` (type 41, xid ``-1 - xid`` as the breaker family's reports) is
``n:u16`` then ``token_id:i64`` a row, and is not answered.

A session holds, per connection, the ids of the rows that came back OK, each
with the time from which it may go back: the reply's time plus a hold drawn
from the seed (``hold_ms: [lo, hi]`` of the traffic file, by connection, in
the order the connection's replies came). ``encode`` puts every id whose
time has come in front of the connection's next ACQUIRE frame, as RELEASE
frames, and forgets it. A lost frame's ids never reached the session and are
never released (upstream: the server expires them, ``resourceTimeout``).
"""

from __future__ import annotations

import struct
import sys
import threading
import time

import numpy as np

from cellbench import wire
from cellbench.deploy import OK
from cellbench.families import flow

ACQUIRE, RELEASE = 40, 41
ACQ_ROW = np.dtype([("status", "i1"), ("remaining", ">i4"), ("wait_ms", ">i4"),
                    ("token_id", ">i8")])
MAX_ROWS_PER_FRAME = wire.MAX_ROWS_PER_FRAME
MAX_IDS_PER_RELEASE = (65535 - 5 - 2) // 8
SINGLE_REPLIES = ((), wire.SINGLE_RSP)
BATCH_REPLIES = ((ACQUIRE,), ACQ_ROW)

Mix = flow.Mix


class Deployment(flow.Deployment):
    pass


Deployment.family = sys.modules[__name__]


def encode_batch(xid: int, flow_ids, counts) -> bytes:
    """One ACQUIRE frame: BATCH_FLOW's bytes under this family's type."""
    raw = bytearray(wire.encode_batch(xid, flow_ids, counts))
    raw[6] = ACQUIRE
    return bytes(raw)


def encode_release(xid: int, token_ids) -> bytes:
    ids = np.asarray(token_ids, ">i8")
    return struct.pack(">HibH", 5 + 2 + 8 * len(ids), -1 - int(xid), RELEASE,
                       len(ids)) + ids.tobytes()


def encode_singles(first_xid: int, *cols):
    raise NotImplementedError("a token is acquired in batch frames only")


class Session:
    def __init__(self, tr: dict, dep, seed: int, proc: int,
                 n_connections: int):
        self.hold_s = [float(ms) / 1000.0 for ms in tr["hold_ms"]]
        self.rng = [np.random.default_rng([int(seed), int(proc), ci, 4099])
                    for ci in range(n_connections)]
        self.locks = [threading.Lock() for _ in range(n_connections)]
        self.held = [[] for _ in range(n_connections)]  # (ids, free from)
        # what the tests read: from when each id that came may go back, and
        # the frames given up
        self.came = [{} for _ in range(n_connections)]
        self.lost_xids = []

    def encode(self, ci: int, xid: int, flow_ids, counts) -> bytes:
        now = time.monotonic()
        due, later = [], []
        with self.locks[ci]:
            for ids, at in self.held[ci]:
                free = at <= now
                due.append(ids[free])
                if not free.all():
                    later.append((ids[~free], at[~free]))
            self.held[ci] = later
        ids = np.concatenate(due) if due else ()
        out = b""
        for k in range(0, len(ids), MAX_IDS_PER_RELEASE):
            out += encode_release(xid, ids[k:k + MAX_IDS_PER_RELEASE])
        return out + encode_batch(xid, flow_ids, counts)

    def back(self, ci: int, xid: int, cols, reply_rows, t: float) -> None:
        ids = reply_rows["token_id"][reply_rows["status"] == OK].astype(
            np.int64)
        if not len(ids):
            return
        lo, hi = self.hold_s
        free = t + self.rng[ci].uniform(lo, hi, len(ids))
        with self.locks[ci]:
            self.held[ci].append((ids, free))
            self.came[ci].update(zip(ids.tolist(), free.tolist()))

    def lost(self, ci: int, xid: int) -> None:
        self.lost_xids.append(int(xid))
