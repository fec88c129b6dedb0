"""Always-on flight recorder: per-thread fixed-size struct rings.

Every hop of the serving pipeline — both front doors, the batcher, the
device step boundary, the reply lanes, and the lease/hierarchy/MOVE control
paths — drops a tiny ``(t_ns, stage, xid, shard, aux)`` event into a ring
owned by the recording thread. The discipline mirrors ``chaos/``:

- **Disarmed (the default)** the entire subsystem is ONE module-attribute
  read and branch per hop (``if _TR.ARMED: ...``) — no lock, no call, no
  allocation. This is what keeps the trace-off overhead inside the ≤2%
  serve_smoke gate.
- **Armed** each hop appends one 24-byte row to a thread-local numpy struct
  ring (no lock: one writer per ring) and the write head wraps, so memory
  is fixed no matter how long the recorder runs. Data-plane events are
  further gated by an xid-hash sample (``sample_xid``), so arming at a low
  rate on a production server records a representative slice, not the
  firehose.

Rings are registered process-wide so :mod:`sentinel_tpu.trace.spans` can
assemble per-xid spans across threads and :mod:`sentinel_tpu.trace.blackbox`
can dump the last N seconds post-mortem. A ring whose thread died mid-write
is still readable — readers treat rows as advisory (torn tails drop out in
span assembly), never as a consistency contract.

Env arming (mirrors ``SENTINEL_CHAOS``): ``SENTINEL_TRACE=1`` arms at
import, ``SENTINEL_TRACE_SAMPLE=0.01`` sets the xid sample fraction.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

# -- stage codes (aux meaning in parens) --------------------------------------
CLIENT_IN = 1    # frame decoded / pulled off a door (aux = rows)
ENQUEUE = 2      # frame handed to the batching queue (aux = queue depth)
DISPATCH = 3     # frame's batch entered the device dispatch (aux = batch rows)
DEVICE_IN = 4    # device step submitted, service lock released (aggregate,
#                  xid=0; aux = rows; shard = PARAM_LANE for a hot-parameter
#                  dispatch, whose rows are its (request, value) rows)
DEVICE_OUT = 5   # verdicts on the host AND counted (record_verdict_batch done;
#                  the stat-log passes follow) (aggregate, xid=0; aux = rows;
#                  shard = lane | the step's live arm bits << ARM_SHIFT). On
#                  the native lane the counting runs after the reply was
#                  submitted, so there it FOLLOWS the dispatch's REPLY_OUT
REPLY_OUT = 6    # frame's reply submitted to its door (aux = rows): parked in
#                  the door's outbox, NOT on the wire; the native TCP door's
#                  IO thread sends it later (its always-on ``door_out_ms``)
SHED = 7         # frame/rows refused (aux = shed-reason index)
FUSE = 8         # fusion ladder stacked frames (aggregate; aux = depth)
LEASE = 9        # lease grant/renew/return on the server (aux = tokens)
LEASE_LOCAL = 10  # client-local admission against a held lease (aux = n)
HIER = 11        # hierarchy share op (demand/grant/renew/return)
MOVE = 12        # MOVE begin/commit/abort (aux = phase: 0/1/2)
PROMOTE = 13     # standby promoted to primary
BROWNOUT = 14    # admission ladder escalated (aux = level)
SHM_POLL = 15    # shm ring door poll/doorbell activity (aux = frames)
OUTCOME = 16     # a completion report ingested: its outcome step issued and
#                  the service lock released (aux = rows accepted; shard = the
#                  ingest's sequence number, which joins it to OUTCOME_IN)
PARAM_LANE = 1   # ``shard`` of the DEVICE_IN / DEVICE_OUT of a param dispatch
# ... and of a concurrency dispatch (acquire and release rows; aux = both)
CONCURRENT_LANE = 2
# DEVICE_OUT of a flow dispatch: ``shard >> ARM_SHIFT`` holds the bits of the
# decide step's cond-gated arms that took their live branch
# (``engine.decide.ARM_SHAPING | ARM_PACING | ARM_OCCUPY``), ``shard &
# (1 << ARM_SHIFT) - 1`` the lane
ARM_SHIFT = 4
# Phase boundaries inside one dispatch (aggregate, xid=0). Each marks the END
# of a phase; ``shard`` carries the service's id and ``aux`` its dispatch
# sequence number (taken under the service lock), so one dispatch's
# boundaries join across the dispatching thread's ring and the
# materializing thread's (``spans.dispatch_phases``). In time order:
# PERMIT, PREP, LOCKED, DEVICE_IN | REPLY_TAKEN, READY, FETCHED, ACCOUNT,
# DEVICE_OUT (the last four are written by the account half with their own
# stamps; on the native lane the reply goes out between FETCHED and ACCOUNT).
PERMIT = 17      # device permit acquired (native lane only; aux = wait, us)
PREP = 18        # host prep done, about to ask for the service lock
LOCKED = 19      # service lock acquired
READY = 20       # the verdict buffer on the host (step and copy finished)
FETCHED = 21     # request-order verdict arrays built (unpack, unsort, MOVED)
ACCOUNT = 24     # the account half began (its end is DEVICE_OUT)
COMPILE = 22     # a backend compile ended (aux = ms)
OUTCOME_IN = 23  # a completion report reached the server: t_ns is when its
#                  door queued it, or the in-process call began (xid = the
#                  report's; aux = its rows; shard as its OUTCOME's). The
#                  span OUTCOME_IN -> OUTCOME is the report's age at ingest
RX = 25          # the native TCP door read the frame's last byte: t_ns is the
#                  door's own stamp (its IO thread's, back-dated as
#                  OUTCOME_IN's), written by the intake lane beside CLIENT_IN
#                  (aux = rows). RX -> CLIENT_IN is door_in + door_wake
REPLY_TAKEN = 26  # a native reply lane's get() returned with a dispatched
#                  group (aggregate, xid=0; aux = its wait in the reply queue,
#                  us, as PERMIT's; the lane's next READY is its dispatch's).
#                  Splits DEVICE_IN -> READY into reply-queue wait and what
#                  was left of the device step
LANE_TURN = 27   # the native device lane begins a dispatch of another kind
#                  than its last, or from the pull it had set aside in
#                  ``held`` (aggregate, xid=0; shard = the kind's lane: 0 flow,
#                  PARAM_LANE, CONCURRENT_LANE; aux = us that pull waited in
#                  ``held``, 0 when the turn did not begin from it)

STAGE_NAMES: Dict[int, str] = {
    CLIENT_IN: "client_in",
    ENQUEUE: "enqueue",
    DISPATCH: "dispatch",
    DEVICE_IN: "device_in",
    DEVICE_OUT: "device_out",
    REPLY_OUT: "reply_out",
    SHED: "shed",
    FUSE: "fuse",
    LEASE: "lease",
    LEASE_LOCAL: "lease_local",
    HIER: "hier",
    MOVE: "move",
    PROMOTE: "promote",
    BROWNOUT: "brownout",
    SHM_POLL: "shm_poll",
    OUTCOME: "outcome",
    PERMIT: "permit",
    PREP: "prep",
    LOCKED: "locked",
    READY: "ready",
    FETCHED: "fetched",
    COMPILE: "compile",
    OUTCOME_IN: "outcome_in",
    ACCOUNT: "account",
    RX: "rx",
    REPLY_TAKEN: "reply_taken",
    LANE_TURN: "lane_turn",
}

# one ring row: 24 bytes, fixed
_EVENT_DTYPE = np.dtype(
    [("t_ns", "<i8"), ("xid", "<i8"), ("stage", "<i2"), ("shard", "<i2"),
     ("aux", "<i4")]
)

DEFAULT_RING_EVENTS = 8192  # per thread; power of two (mask-wrapped)

# -- the armed flag: the ONLY thing hot paths read when tracing is off --------
ARMED: bool = False

# xid sampling: a data-plane xid is recorded iff hash(xid) < _SAMPLE_LIMIT.
# Fibonacci-hash the xid so adjacent xids (every client counts up) spread
# uniformly over the 32-bit range; limit = fraction × 2^32.
_HASH_MULT = 2654435761
_SAMPLE_LIMIT = 1 << 32  # sample everything by default
_SAMPLE_FRACTION = 1.0

_REG_LOCK = threading.Lock()
_RINGS: List["_ThreadRing"] = []
_TLS = threading.local()
_ARMED_AT_NS: Optional[int] = None


class _ThreadRing:
    """One thread's event ring. Single-writer; readers are advisory."""

    __slots__ = ("buf", "idx", "mask", "thread_name")

    def __init__(self, capacity: int, thread_name: str):
        self.buf = np.zeros(capacity, dtype=_EVENT_DTYPE)
        self.idx = 0  # monotonically increasing write head
        self.mask = capacity - 1
        self.thread_name = thread_name

    def write(self, t_ns: int, stage: int, xid: int, shard: int,
              aux: int) -> None:
        i = self.idx & self.mask
        row = self.buf[i]
        row["t_ns"] = t_ns
        row["xid"] = xid
        row["stage"] = stage
        row["shard"] = shard
        row["aux"] = aux
        self.idx += 1

    def rows(self) -> np.ndarray:
        """Valid rows, oldest→newest write order (advisory under a live
        writer; a torn tail shows as a t_ns=0 or stale row and is filtered
        by readers)."""
        n = min(self.idx, self.mask + 1)
        if n == 0:
            return self.buf[:0]
        if self.idx <= self.mask + 1:
            return self.buf[:n]
        head = self.idx & self.mask
        return np.concatenate([self.buf[head:], self.buf[:head]])


def _ring() -> _ThreadRing:
    r = getattr(_TLS, "ring", None)
    if r is None:
        r = _ThreadRing(DEFAULT_RING_EVENTS, threading.current_thread().name)
        _TLS.ring = r
        with _REG_LOCK:
            _RINGS.append(r)
    return r


# -- recording (call sites guard with `if ring.ARMED:`) -----------------------
def sample_xid(xid: int) -> bool:
    """True when this xid is inside the sampled slice."""
    return ((xid * _HASH_MULT) & 0xFFFFFFFF) < _SAMPLE_LIMIT


def record(stage: int, xid: int = 0, shard: int = 0, aux: int = 0,
           t_ns: Optional[int] = None) -> None:
    """Append one event. Data-plane events (xid != 0) honor the sample;
    control-plane events (xid == 0) always record while armed. ``t_ns``
    (``time.monotonic_ns()``) back-dates a boundary whose owner could not
    write it when it passed (readers sort by time, not by write order)."""
    if xid and ((xid * _HASH_MULT) & 0xFFFFFFFF) >= _SAMPLE_LIMIT:
        return
    _ring().write(
        time.monotonic_ns() if t_ns is None else t_ns, stage, xid, shard, aux
    )


def record_many(stage: int, xids, shard: int = 0, aux: int = 0,
                t_ns=None) -> None:
    """One event per sampled xid in ``xids`` (a batch hop touching many
    frames). Python-loop cost is paid only while armed and only for
    sampled xids. ``t_ns``, one stamp per xid, back-dates each event to a
    boundary its owner could not write (a 0 leaves that xid out)."""
    r = _ring()
    lim = _SAMPLE_LIMIT
    if t_ns is not None:
        for x, t in zip(xids, t_ns):
            x, t = int(x), int(t)
            if t and ((x * _HASH_MULT) & 0xFFFFFFFF) < lim:
                r.write(t, stage, x, shard, aux)
        return
    t = time.monotonic_ns()
    for x in xids:
        x = int(x)
        if ((x * _HASH_MULT) & 0xFFFFFFFF) < lim:
            r.write(t, stage, x, shard, aux)


# -- arming -------------------------------------------------------------------
def arm(sample: float = 1.0) -> None:
    """Arm the recorder; ``sample`` is the fraction of xids recorded."""
    global ARMED, _SAMPLE_LIMIT, _SAMPLE_FRACTION, _ARMED_AT_NS
    sample = min(1.0, max(0.0, float(sample)))
    _SAMPLE_FRACTION = sample
    _SAMPLE_LIMIT = int(sample * (1 << 32))
    _ARMED_AT_NS = time.monotonic_ns()
    ARMED = True


def disarm() -> None:
    global ARMED
    ARMED = False


def status() -> dict:
    with _REG_LOCK:
        threads = [
            {"thread": r.thread_name,
             "events": int(min(r.idx, r.mask + 1)),
             "dropped": int(max(0, r.idx - (r.mask + 1)))}
            for r in _RINGS
        ]
    return {
        "armed": ARMED,
        "sample": _SAMPLE_FRACTION,
        "ringEvents": DEFAULT_RING_EVENTS,
        "threads": threads,
        "totalEvents": sum(t["events"] for t in threads),
    }


def reset_for_tests() -> None:
    """Disarm and drop every registered ring (tests/benches only — live
    threads re-register their ring on the next armed record)."""
    global _SAMPLE_LIMIT, _SAMPLE_FRACTION, _ARMED_AT_NS
    disarm()
    _SAMPLE_LIMIT = 1 << 32
    _SAMPLE_FRACTION = 1.0
    _ARMED_AT_NS = None
    with _REG_LOCK:
        _RINGS.clear()
    if getattr(_TLS, "ring", None) is not None:
        _TLS.ring = None


# -- reading ------------------------------------------------------------------
def events(
    xid: Optional[int] = None,
    since_ns: Optional[int] = None,
    stages: Optional[set] = None,
) -> List[dict]:
    """Snapshot matching events from EVERY ring (live or torn), sorted by
    time. Rows with t_ns == 0 (never written / torn tail) are dropped."""
    with _REG_LOCK:
        rings = list(_RINGS)
    out: List[dict] = []
    for r in rings:
        rows = r.rows()
        if rows.shape[0] == 0:
            continue
        keep = rows["t_ns"] > 0
        if since_ns is not None:
            keep &= rows["t_ns"] >= since_ns
        if xid is not None:
            keep &= rows["xid"] == xid
        for row in rows[keep]:
            st = int(row["stage"])
            if stages is not None and st not in stages:
                continue
            out.append({
                "t_ns": int(row["t_ns"]),
                "stage": STAGE_NAMES.get(st, str(st)),
                "xid": int(row["xid"]),
                "shard": int(row["shard"]),
                "aux": int(row["aux"]),
                "thread": r.thread_name,
            })
    out.sort(key=lambda e: e["t_ns"])
    return out


def sampled_xids(limit: int = 256) -> List[int]:
    """Distinct data-plane xids seen at client_in, newest first."""
    seen: Dict[int, int] = {}
    for e in events(stages={CLIENT_IN}):
        if e["xid"]:
            seen[e["xid"]] = e["t_ns"]
    ordered = sorted(seen, key=seen.get, reverse=True)
    return ordered[:limit]


def _env_arm() -> None:
    if os.environ.get("SENTINEL_TRACE", "") not in ("", "0"):
        try:
            frac = float(os.environ.get("SENTINEL_TRACE_SAMPLE", "1.0"))
        except ValueError:
            frac = 1.0
        arm(sample=frac)


_env_arm()
