"""The correctness probe: exact verdicts through the door the window used.

After the measured window closes, the probe sends, through the same door to
the same server, frames of the cell's own kind and size (BATCH_FLOW frames
of the mix's ``frame_rows`` with the mix's acquire values, or one-token FLOW
frames) on flows of the two namespaces no traffic touches, and compares
every verdict with ``cellbench/reference.py``. Each check starts from an
empty window and lasts far less than one, so its verdicts follow from the
rules alone. Data (positions, acquires, background flows) is drawn from
``--seed``. Every comparison has the limit 0 mismatches.

Checks:

    tight   rows of count-C flows (C from the file's ``tight_counts``), each
            flow with one acquire size, scattered among unmetered rows: the
            first floor(C/a) rows of a flow pass, in arrival order
    big     count 5000 sent 6000: exactly the first 5000 pass (a count past
            256 is what an 8-bit mantissa gets wrong)
    guard   32768 rows into an idle namespace against the 30000/s guard:
            exactly 30000 pass, 2768 TOO_MANY_REQUEST
    paced   a RATE_LIMITER flow, 60 rows in one frame: OK, then waits of
            10, 20, ... 500 ms, then BLOCKED (batch frames only: one-token
            frames arrive at different times, so the waits are not fixed)
"""

from __future__ import annotations

import socket
import threading
import time

import numpy as np

from cellbench import deploy, reference, wire

MAX_CHECK_S = 0.85  # a check slower than this has left its window


def exchange(port: int, payloads, n_rows: int, single: bool,
             timeout_s: float = 20.0):
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
    split = wire.Splitter()
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


def _payloads(ids, acq, frame_rows: int, single: bool, xid0: int):
    if single:
        arr = wire.encode_singles(xid0, ids, acq)
        return [arr[i:i + 2048].tobytes() for i in range(0, len(arr), 2048)]
    return [wire.encode_batch(xid0 + k, ids[i:i + frame_rows],
                              acq[i:i + frame_rows])
            for k, i in enumerate(range(0, len(ids), frame_rows))]


class Probe:
    def __init__(self, port: int, dep, tr: dict, seed: int, say=print,
                 probe_set: int = 0):
        self.port, self.dep, self.tr, self.say = port, dep, tr, say
        self.rng = np.random.default_rng([int(seed), 7919])
        self.single = tr["msg"] == "single"
        self.frame_rows = 1 if self.single else min(
            int(tr["frame_rows"]), wire.MAX_ROWS_PER_FRAME)
        self.flows = dep.probe_set(probe_set)
        self.acq_values = [int(a) for a in tr["acquire"]["values"]]
        self.xid = 1_900_000_000
        self.checks = []
        self.ref = reference.for_deployment(dep)
        # The reference decides the whole probe at one instant: every check
        # has flows of its own, and the probe namespace's guard window sees
        # all of them, as the server's does while the probe lasts under one
        # window. That holds only while the probe's rows fit the guard.
        n_rows = (2 * sum(c for _f, c in self.flows["tight"]) + 2048
                  + int(self.flows["big"][1]) + 1000 + 60)
        if n_rows >= dep.ns_max_qps * dep.window_ms / 1000:
            raise ValueError(f"the probe's {n_rows} rows do not fit the "
                             f"namespace guard of {dep.ns_max_qps}/s")

    def _background(self, ns: int, n: int):
        """Unmetered plain flows of a probe namespace, the mix's acquires."""
        lo = len(self.dep.metered_counts)
        rank = self.rng.integers(lo, self.dep.flows_per_namespace(), size=n)
        acq = self.rng.choice(self.acq_values, size=n)
        return self.dep.flow_id(ns, rank), acq.astype(np.int32)

    def _send(self, ids, acq):
        ids = np.asarray(ids, np.int64)
        acq = np.asarray(acq, np.int32)
        pl = _payloads(ids, acq, self.frame_rows, self.single, self.xid)
        self.xid += len(ids) + 16
        return exchange(self.port, pl, len(ids), self.single)

    def _record(self, name: str, rows: int, mismatches: int, took: float,
                note: str = "") -> None:
        ok = mismatches == 0
        self.checks.append({"check": name, "rows": rows,
                            "mismatches": int(mismatches), "limit": 0,
                            "seconds": took, "ok": ok})
        self.say(f"probe {name}: {rows} rows, {mismatches} mismatches "
                 f"(limit 0), {took * 1e3:.1f} ms{note}")

    def _want(self, ids, acq):
        """The reference's verdicts for rows that arrive together."""
        return self.ref.decide_frame(10_000, ids, acq)

    # -- the checks ----------------------------------------------------------
    def tight(self) -> None:
        ns = self.dep.probe_namespaces[0]
        small = [a for a in self.acq_values if a <= 5]
        parts_i, parts_a = [], []
        for fid, count in self.flows["tight"]:
            a = int(self.rng.choice(small))
            n = int(count // a) + 10
            parts_i.append(np.full(n, fid, np.int64))
            parts_a.append(np.full(n, a, np.int32))
        t_ids = np.concatenate(parts_i)
        t_acq = np.concatenate(parts_a)
        perm = self.rng.permutation(len(t_ids))  # flows interleaved
        t_ids, t_acq = t_ids[perm], t_acq[perm]
        total = -(-2 * len(t_ids) // self.frame_rows) * self.frame_rows
        ids, acq = self._background(ns, total)
        at = np.sort(self.rng.choice(total, size=len(t_ids), replace=False))
        ids[at], acq[at] = t_ids, t_acq
        status, _wait, took = self._send(ids, acq)
        want, _ = self._want(ids, acq)
        bad = int((status != np.asarray(want, np.int8)).sum())
        self._record("tight", total, bad, took)

    def big(self) -> None:
        fid, count = self.flows["big"]
        n = int(count) + 1000
        ids = np.full(n, fid, np.int64)
        acq = np.ones(n, np.int32)
        status, _wait, took = self._send(ids, acq)
        want, _ = self._want(ids, acq)
        bad = int((status != np.asarray(want, np.int8)).sum())
        self._record("big", n, bad, took,
                     f"; {int((status == deploy.OK).sum())} OK of {n}, "
                     f"count {int(count)}")

    def guard(self) -> None:
        ns = self.dep.probe_namespaces[1]
        n = int(self.dep.ns_max_qps * self.dep.window_ms / 1000) + 2768
        ids, _ = self._background(ns, n)
        acq = np.ones(n, np.int32)
        status, _wait, took = self._send(ids, acq)
        n_ok = int((status == deploy.OK).sum())
        n_many = int((status == deploy.TOO_MANY).sum())
        budget = n - 2768
        if took <= MAX_CHECK_S:
            # which rows are refused follows slot order inside a dispatch,
            # so the guarantee compared is the count
            bad = abs(n_ok - budget) + abs(n_many - 2768)
            note = f"; {n_ok} OK, {n_many} TOO_MANY_REQUEST, budget {budget}"
        else:
            # the burst outlasted its window: early rows have left it, so
            # only the bounds hold (never fewer than the budget admitted,
            # nothing but OK and TOO_MANY_REQUEST)
            bad = max(0, budget - n_ok) + (n - n_ok - n_many)
            note = (f"; slow burst, bounds only: {n_ok} OK >= {budget}, "
                    f"{n_many} TOO_MANY_REQUEST")
        self._record("guard", n, bad, took, note)

    def paced(self) -> None:
        if self.single:
            self.say("probe paced: skipped, one-token frames arrive at "
                     "different times so the waits are not fixed")
            return
        fid, _count = self.flows["paced"]
        ids = np.full(60, fid, np.int64)
        acq = np.ones(60, np.int32)
        status, wait, took = self._send(ids, acq)
        want_s, want_w = self._want(ids, acq)
        bad = int((status != np.asarray(want_s, np.int8)).sum())
        waits = status == deploy.SHOULD_WAIT
        bad += int((wait[waits] != np.asarray(want_w, np.int32)[waits]).sum())
        self._record("paced", 60, bad, took)

    def run(self) -> dict:
        for check in (self.tight, self.big, self.guard, self.paced):
            try:
                check()
            except (RuntimeError, OSError) as e:
                self.checks.append({"check": check.__name__, "ok": False,
                                    "error": str(e)})
                self.say(f"probe {check.__name__}: FAILED to run: {e}")
        return {"ok": all(c["ok"] for c in self.checks),
                "checks": self.checks}
