"""The correctness probe: exact verdicts through the door the window used.

After the measured window closes, the probe sends, through the same door to
the same server, frames of the cell's own kind and size (batch frames of the
mix's ``frame_rows``, or one-row frames) on rules no traffic touches, and
compares every verdict with the family's plain reference. Each check starts
from an empty window and lasts far less than one, so its verdicts follow from
the rules alone. Data is drawn from ``--seed``. Every comparison has the
limit 0 mismatches.

Which rows a check sends and what it expects is the deployment's family's
(``probe_checks`` of ``cellbench/families/<family>.py``, where the checks are
described); this module sends, times, records and survives a check that
could not run.
"""

from __future__ import annotations

import socket
import threading
import time

import numpy as np

from cellbench import wire


def exchange(port: int, payloads, n_rows: int, single: bool,
             replies=(), timeout_s: float = 20.0):
    """Send ``payloads`` (bytes objects, in order) on one connection and
    collect ``n_rows`` verdict rows in request order. Returns ``(status,
    wait_ms, seconds)``; raises on a timeout or a lost connection."""
    sock = socket.create_connection(("127.0.0.1", port), timeout=10)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    err = []

    def sender():
        try:
            for p in payloads:
                sock.sendall(p)
        except OSError as e:
            err.append(e)

    th = threading.Thread(target=sender, daemon=True)
    split = wire.Splitter(*replies)
    got = {}  # xid -> rows
    have = 0
    t0 = time.monotonic()
    th.start()
    sock.settimeout(0.5)
    try:
        while have < n_rows:
            if time.monotonic() - t0 > timeout_s or err:
                raise RuntimeError(
                    f"probe got {have} of {n_rows} rows back ({err})")
            try:
                data = sock.recv(1 << 18)
            except socket.timeout:
                continue
            if not data:
                raise RuntimeError("probe connection closed by the server")
            batch, singles = split.feed(data)
            for xid, rows in batch:
                got[xid] = rows
                have += len(rows)
            if singles is not None:
                for r in singles:
                    got[int(r["xid"])] = r
                have += len(singles)
        took = time.monotonic() - t0
    finally:
        th.join(timeout=5)
        sock.close()
    order = sorted(got)
    if single:
        status = np.array([got[x]["status"] for x in order], np.int8)
        wait = np.array([got[x]["wait_ms"] for x in order], np.int32)
    else:
        status = np.concatenate([got[x]["status"] for x in order])
        wait = np.concatenate([got[x]["wait_ms"] for x in order])
    return status.astype(np.int8), wait.astype(np.int32), took


def _payloads(family, cols, frame_rows: int, single: bool, xid0: int):
    if single:
        arr = family.encode_singles(xid0, *cols)
        return [arr[i:i + 2048].tobytes() for i in range(0, len(arr), 2048)]
    return [family.encode_batch(xid0 + k, *[c[i:i + frame_rows]
                                            for c in cols])
            for k, i in enumerate(range(0, len(cols[0]), frame_rows))]


class Probe:
    def __init__(self, port: int, dep, tr: dict, seed: int, say=print,
                 probe_set: int = 0):
        self.port, self.dep, self.tr, self.say = port, dep, tr, say
        self.family = dep.family
        self.rng = np.random.default_rng([int(seed), 7919])
        self.single = tr["msg"] == "single"
        self.frame_rows = 1 if self.single else min(
            int(tr["frame_rows"]), self.family.MAX_ROWS_PER_FRAME)
        self.probe_set = probe_set
        self.xid = 1_900_000_000
        self.checks = []

    def send(self, *cols):
        """The rows ``cols`` through the door, in frames of the cell's own
        kind and size: ``(status, wait_ms, seconds)`` in request order."""
        pl = _payloads(self.family, cols, self.frame_rows, self.single,
                       self.xid)
        self.xid += len(cols[0]) + 16
        return exchange(self.port, pl, len(cols[0]), self.single,
                        (self.family.SINGLE_REPLIES,
                         self.family.BATCH_REPLIES))

    def record(self, name: str, rows: int, mismatches: int, took: float,
               note: str = "") -> None:
        ok = mismatches == 0
        self.checks.append({"check": name, "rows": rows,
                            "mismatches": int(mismatches), "limit": 0,
                            "seconds": took, "ok": ok})
        self.say(f"probe {name}: {rows} rows, {mismatches} mismatches "
                 f"(limit 0), {took * 1e3:.1f} ms{note}")

    def run(self) -> dict:
        for check in self.family.probe_checks(self):
            try:
                check()
            except (RuntimeError, OSError) as e:
                self.checks.append({"check": check.__name__, "ok": False,
                                    "error": str(e)})
                self.say(f"probe {check.__name__}: FAILED to run: {e}")
        return {"ok": all(c["ok"] for c in self.checks),
                "checks": self.checks}
