"""Sampled end-to-end spans assembled on demand from the flight-recorder
rings.

No wire change: the xid already rides every frame of every transport, so a
span is just "every ring event carrying this xid, time-ordered". Assembly
is a read-side join across ALL thread rings — intake shard, batcher,
device lane, reply lane each recorded their hop into their own ring, and
the xid stitches them back into one request timeline.

Spans are advisory by construction: a wrapped ring has already evicted the
oldest hops, and a thread that died mid-record leaves a torn tail. Both
show up as an *incomplete* span (``complete=False`` with the covered
stages listed), never as an exception — the completeness check is the
consumer's gate, not the assembler's.
"""

from __future__ import annotations

import json
import os
import time
from typing import List, Optional

from sentinel_tpu.trace import ring as _R

# the client→reply contract: a complete span enters at the door and leaves
# through a reply (or an explicit shed refusal, which IS the reply)
_ENTRY_STAGE = "client_in"
_EXIT_STAGES = ("reply_out", "shed")


def assemble(xid: int) -> Optional[dict]:
    """Span for one xid, or None when no ring holds any event for it
    (unsampled xid, or the ring wrapped past it)."""
    evs = _R.events(xid=xid)
    if not evs:
        return None
    stages = [e["stage"] for e in evs]
    t0, t1 = evs[0]["t_ns"], evs[-1]["t_ns"]
    complete = _ENTRY_STAGE in stages and any(
        s in stages for s in _EXIT_STAGES
    )
    return {
        "xid": xid,
        "startNs": t0,
        "durationUs": round((t1 - t0) / 1_000.0, 3),
        "stages": stages,
        "complete": complete,
        "events": evs,
    }


def assemble_recent(limit: int = 64) -> List[dict]:
    """Spans for the most recently sampled xids (newest first)."""
    out = []
    for xid in _R.sampled_xids(limit=limit):
        sp = assemble(xid)
        if sp is not None:
            out.append(sp)
    return out


def completeness(spans: List[dict]) -> dict:
    """The trace-smoke gate: fraction of assembled spans covering
    client-in → reply-out."""
    total = len(spans)
    complete = sum(1 for s in spans if s["complete"])
    return {
        "spans": total,
        "complete": complete,
        "fraction": (complete / total) if total else None,
    }


# dispatch-side boundaries land in the dispatching thread's ring, the rest
# in the materializing thread's; (service id, sequence number) joins them
_PHASE_STAGES = {_R.PERMIT, _R.PREP, _R.LOCKED, _R.DEVICE_IN,
                 _R.REPLY_TAKEN, _R.READY, _R.FETCHED, _R.ACCOUNT,
                 _R.DEVICE_OUT}


def dispatch_phases(since_ns: Optional[int] = None,
                    limit: Optional[int] = None) -> List[dict]:
    """Per-dispatch phase durations (ms) from the rings, oldest first.

    One entry per ``(service, seq)`` seen at ``locked``: ``prepMs`` (permit
    granted → prep done; None without the native lane's ``permit`` event),
    ``permitWaitMs`` (the ``permit`` event's own aux), ``lockWaitMs``,
    ``launchMs`` (lock held, to ``device_in``), ``waitMs`` (``device_in`` →
    the verdict buffer on the host) and its two halves on the native lane,
    which the always-on ``reply_queue_wait_ms`` and ``device_wait_ms`` keep
    apart: ``replyQueueWaitMs`` (``device_in`` → ``reply_taken``, a reply
    lane's ``get()`` returned) and ``deviceWaitMs`` (``reply_taken`` →
    ``ready``: what was left of the device step); both None where no reply
    lane took the dispatch (the asyncio door, a synchronous call).
    ``fetchMs``, ``accountMs`` (``account``, the account
    half's own start, to ``device_out``: the verdict counters; the stat-log
    passes after it are in the always-on ``account_ms`` only; the reply the
    native lane submits between ``fetched`` and ``account`` is in neither).
    ``complete`` when every boundary from ``prep`` to ``device_out`` was
    found; a wrapped ring or a dispatch still in flight leaves the missing
    phases None."""
    by_thread: dict = {}
    for e in _R.events(since_ns=since_ns, stages=_PHASE_STAGES):
        by_thread.setdefault(e["thread"], []).append(e)
    out: dict = {}

    def ms(a, b):
        return None if a is None or b is None else (b - a) / 1e6

    for thread, evs in by_thread.items():
        permit = None  # the last permit not yet claimed by a dispatch
        taken = None  # the last reply_taken not yet claimed by a ``ready``
        cur = None  # the dispatch whose boundaries this thread is writing
        for e in evs:
            st = e["stage"]
            if st == "permit":
                permit = e
            elif st == "reply_taken":
                taken = e
            elif st in ("prep", "ready"):
                cur = out.setdefault((e["shard"], e["aux"]), {
                    "service": e["shard"], "seq": e["aux"]})
                cur[st] = e["t_ns"]
                cur[st + "Thread"] = thread
                if st == "prep" and permit is not None:
                    cur["permit"] = permit["t_ns"]
                    cur["permitWaitMs"] = permit["aux"] / 1e3
                    permit = None
                if st == "ready" and taken is not None:
                    cur["reply_taken"] = taken["t_ns"]
                    taken = None
            elif cur is not None and (e["shard"], e["aux"]) == (
                    cur["service"], cur["seq"]):
                cur[st] = e["t_ns"]  # locked, fetched, account
            elif cur is not None and st == "device_in" and "locked" in cur:
                cur.setdefault("device_in", e["t_ns"])
                cur["rows"] = e["aux"]
            elif cur is not None and st == "device_out" and "fetched" in cur:
                cur.setdefault("device_out", e["t_ns"])
    rows = []
    for d in sorted(out.values(), key=lambda d: d.get("prep", d.get("ready"))):
        g = d.get
        rows.append({
            "service": d["service"], "seq": d["seq"], "rows": g("rows"),
            "startNs": g("permit", g("prep")),
            "dispatchThread": g("prepThread"),
            "replyThread": g("readyThread"),
            "permitWaitMs": g("permitWaitMs"),
            "prepMs": ms(g("permit"), g("prep")),
            "lockWaitMs": ms(g("prep"), g("locked")),
            "launchMs": ms(g("locked"), g("device_in")),
            "waitMs": ms(g("device_in"), g("ready")),
            "replyQueueWaitMs": ms(g("device_in"), g("reply_taken")),
            "deviceWaitMs": ms(g("reply_taken"), g("ready")),
            "fetchMs": ms(g("ready"), g("fetched")),
            "accountMs": ms(g("account"), g("device_out")),
            "complete": all(k in d for k in (
                "prep", "locked", "device_in", "ready", "fetched",
                "device_out")),
        })
    return rows if limit is None else rows[-limit:]


def write_artifact(path: str, limit: int = 256,
                   sync: Optional[dict] = None) -> str:
    """Dump recent spans + completeness to a JSON artifact (the profiler
    hook's stop() product). ``sync`` is the hook's clock tie: the
    ``monotonicNs`` its ``sentinel.sync`` annotation carries in the device
    trace; every ``t_ns`` / ``startNs`` here is on that clock. Returns the
    written path."""
    from sentinel_tpu.metrics.exporter import build_info

    spans = assemble_recent(limit=limit)
    doc = {
        "schema": "sentinel-trace-spans/1",
        "wallTime": time.time(),
        "build": build_info(),
        "trace": _R.status(),
        "sync": sync,
        "completeness": completeness(spans),
        "spans": spans,
        "dispatches": dispatch_phases(
            since_ns=(sync or {}).get("monotonicNs"), limit=limit
        ),
    }
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    os.replace(tmp, path)
    return path
