"""One load-generator process: numpy and sockets, never JAX.

Started by ``cellbench/run.py`` with a plan file; builds every frame it will
send from the seed while the server warms up (a family with a ``Session``
has its frames' rows drawn then and their bytes made at send time, from the
replies read so far: ``families/__init__.py``), connects when the server's
port file appears, then takes commands on standard input (``warm``,
``burst <rows>``, ``measure <t0> <seconds>``, ``quit``) and answers each with
one JSON line. The measured window's raw samples go to an ``.npz`` beside
the plan. ``loadgen.py witness`` is the process that only sleeps (see
``witness``).

Failure accounting (ISSUE 23, A.3), all of it at the client:

* attempted - every row the schedule made due inside the window (closed
  loop: every row sent inside it);
* failed - every such row that did not come back as a verdict the device
  decided: a send skipped because the in-flight window was full, no reply
  within ``timeout_ms``, a connection error, a status that is not a decision
  (OVERLOAD, FAIL, STANDBY, MOVED, anything unknown), and a brownout pass:
  status OK with ``remaining == 0`` on an unmetered flow, which only the
  overload ladder's local answer produces (which statuses are decisions and
  what a brownout pass looks like is the deployment's family's to say:
  ``Deployment.ledger_view``);
* latency - from the time the frame was due (closed loop: sent) to its
  reply, every row of a frame carrying the frame's latency, failed rows left
  out of the percentiles.
"""

from __future__ import annotations

import functools
import json
import os
import socket
import sys
import threading
import time

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cellbench import deploy, traffic, wire  # noqa: E402

BIN_S = 0.1  # reply-time bins of the admitted-token ledger
N_STATUS = 16
_SINGLE_POOL = 1 << 16
RUN_S = 0.001  # open loop, one-row frames: the least time between two runs
_SENT_RING = 1 << 16


class Ledger:
    """What one run counts, shared by its connection threads."""

    def __init__(self, dep, t0: float, t_end: float):
        self.dep = dep
        self.t0 = t0
        self.t_end = t_end
        self.lock = threading.Lock()
        self.attempted = 0
        self.failed = {"skipped": 0, "timeout": 0, "connection": 0,
                       "status": 0, "brownout_pass": 0, "short_reply": 0}
        self.decided_in_window = 0
        self.decided = 0
        self.status_hist = np.zeros(N_STATUS, np.int64)
        self.never_rows = 0  # rows the family says can never come back
        self.duplicates = 0
        n_bins = int((t_end - t0) / BIN_S) + 200
        self.admitted = np.zeros(
            (len(dep.ledger_counts()), n_bins), np.float64)
        self.lat_max = np.zeros(n_bins)  # slowest reply of each bin
        self.lat = []  # (latency seconds array, weight array)
        self.lags = []

    def rows_back(self, t: float, lat, cols, status, remaining) -> None:
        """Account the verdicts of the rows ``cols`` that came back at
        ``t``; ``lat`` is one latency for all of them or an array."""
        st = status.astype(np.uint8)
        decided, brown, never, keys, tokens = self.dep.ledger_view(
            cols, st, remaining)
        good = decided & ~brown
        n_good = int(good.sum())
        with self.lock:
            self.status_hist += np.bincount(
                np.minimum(st, N_STATUS - 1), minlength=N_STATUS)
            self.failed["status"] += int((~decided).sum())
            self.failed["brownout_pass"] += int(brown.sum())
            self.never_rows += never
            self.decided += n_good
            if t <= self.t_end:
                self.decided_in_window += n_good
            b = min(max(int((t - self.t0) / BIN_S), 0),
                    self.admitted.shape[1] - 1)
            self.lat_max[b] = max(self.lat_max[b], float(np.max(lat)))
            if len(keys):
                np.add.at(self.admitted[:, b], keys, tokens)
            if n_good:
                if np.ndim(lat) == 0:
                    self.lat.append((np.array([lat]), np.array([n_good])))
                else:
                    self.lat.append((np.asarray(lat)[good],
                                     np.ones(n_good, np.int64)))

    def fail(self, reason: str, rows: int) -> None:
        with self.lock:
            self.failed[reason] += int(rows)

    def summary(self) -> dict:
        lat = (np.concatenate([a for a, _ in self.lat]) if self.lat
               else np.empty(0))
        w = (np.concatenate([b for _, b in self.lat]) if self.lat
             else np.empty(0, np.int64))
        lags = np.concatenate(self.lags) if self.lags else np.empty(0)
        return {
            "attempted": int(self.attempted),
            "failed": dict(self.failed),
            "failed_rows": int(sum(self.failed.values())),
            "decided": int(self.decided),
            "decided_in_window": int(self.decided_in_window),
            "status_hist": self.status_hist.tolist(),
            "never_rows": int(self.never_rows),
            "duplicates": int(self.duplicates),
        }, {"lat_s": lat.astype(np.float64), "lat_w": w.astype(np.int64),
            "lag_s": lags.astype(np.float64), "admitted": self.admitted,
            "lat_max": self.lat_max}


class Conn:
    def __init__(self, port: int, family):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=10)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.settimeout(0.2)
        self.split = wire.Splitter(family.SINGLE_REPLIES,
                                   family.BATCH_REPLIES)
        self.dead = False

    def send(self, data) -> bool:
        try:
            self.sock.settimeout(10.0)
            self.sock.sendall(data)
            self.sock.settimeout(0.2)
            return True
        except OSError:
            self.dead = True
            return False

    def recv(self):
        """``(batch, singles)``, ``None`` on a quiet 0.2 s, ``False`` when
        the connection is gone."""
        try:
            data = self.sock.recv(1 << 18)
        except socket.timeout:
            return None
        except OSError:
            self.dead = True
            return False
        if not data:
            self.dead = True
            return False
        return self.split.feed(data)

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass


class Generator:
    def __init__(self, plan: dict):
        self.plan = plan
        self.t = plan["traffic"]
        self.dep = deploy.load(plan["config_file"],
                               plan.get("family_dirs", ()))
        self.fam = self.dep.family
        self.proc = int(plan["proc"])
        self.n_procs = int(self.t["processes"])
        self.seed = int(plan["seed"])
        self.single = self.t["msg"] == "single"
        self.open = self.t["loop"] == "open"
        self.rows = traffic.frame_rows(self.t)
        self.timeout_s = float(self.t["timeout_ms"]) / 1000.0
        self.next_xid = 1 + self.proc * 200_000_000
        self.conns = []
        # frames whose bytes depend on replies: the family's, if it has one
        # and the traffic file asks for it (``families/__init__.py``)
        make = getattr(self.fam, "Session", None)
        self.session = None if make is None else make(
            self.t, self.dep, self.seed, self.proc,
            int(self.t["connections"]))
        if self.session is not None and self.single:
            raise ValueError(
                "a family's Session encodes batch frames; one-row frames are "
                "sent a run at a time as a packed array (msg: single)")
        self._build(float(plan["seconds"]), float(plan["warm_seconds"]))

    # -- frames, made before the server is up -------------------------------
    def _build(self, seconds: float, warm_seconds: float) -> None:
        mix = self.fam.Mix(self.t, self.dep, self.seed, 1 + self.proc)
        if self.open:
            due = traffic.open_schedule(self.t, seconds)
            mine = np.arange(len(due)) % self.n_procs == self.proc
            # tenants are apportioned over ALL frames, then split by process
            who = self.fam.Mix(self.t, self.dep, self.seed,
                               0).frame_tenants(len(due))[mine]
            self.main = (due[mine], mix.rows(who))
            wdue = traffic.open_schedule(self.t, warm_seconds)
            wmine = np.arange(len(wdue)) % self.n_procs == self.proc
            self.warm = (wdue[wmine], mix.frames(int(wmine.sum())))
        else:
            n_pool = (_SINGLE_POOL * int(self.t["connections"]) if self.single
                      else int(self.t.get("pool_frames", 512)))
            self.pool = mix.frames(n_pool)
        self.burst_mix = mix

    def planned_rows(self) -> int:
        return int(len(self.main[0]) * self.rows) if self.open else 0

    def connect(self, port: int) -> None:
        self.conns = [Conn(port, self.fam)
                      for _ in range(int(self.t["connections"]))]

    def _xids(self, n: int) -> int:
        x0 = self.next_xid
        self.next_xid += n
        return x0

    # -- open loop -----------------------------------------------------------
    def run_open(self, t0: float, due, cols, window: int,
                 seconds: float) -> Ledger:
        n = len(due)
        x0 = self._xids(n)
        conns = self.conns
        ses = self.session
        lost = None
        if self.single:  # one packed array, frame k at ``enc[k]``
            enc = self.fam.encode_singles(x0, *[col[:, 0] for col in cols])
        elif ses is None:
            enc = traffic.encode_frames(self.fam, cols, x0).__getitem__
        else:  # frame k's bytes when it is sent, from the replies so far
            def enc(k: int) -> bytes:
                return ses.encode(k % len(conns), x0 + k,
                                  *[col[k] for col in cols])
            if hasattr(ses, "lost"):
                def lost(k: int) -> None:
                    ses.lost(k % len(conns), x0 + k)
        led = Ledger(self.dep, t0, t0 + seconds)
        led.attempted = n * self.rows
        due_abs = t0 + due
        was_sent = np.zeros(n, bool)
        replied = np.zeros(n, bool)
        state = {"inflight": 0, "done": False}

        def reader(c: Conn) -> None:
            while not state["done"] and not c.dead:
                got = c.recv()
                if not got:
                    continue
                now = time.monotonic()
                if got[1] is not None:
                    self._singles_back(led, state, replied, now, got[1], x0,
                                       due_abs, cols)
                for xid, rows in got[0]:
                    k = xid - x0
                    if not 0 <= k < n:
                        continue
                    with led.lock:
                        dup = replied[k]
                        replied[k] = True
                        if dup:
                            led.duplicates += 1
                        else:
                            state["inflight"] -= 1
                    if dup:
                        continue
                    m = min(len(rows), self.rows)
                    if m < self.rows:
                        led.fail("short_reply", self.rows - m)
                    if ses is not None:
                        ses.back(k % len(conns), xid,
                                 [col[k] for col in cols], rows, now)
                    led.rows_back(now, now - due_abs[k],
                                  [col[k][:m] for col in cols],
                                  rows["status"][:m], rows["remaining"][:m])

        threads = [threading.Thread(target=reader, args=(c,), daemon=True)
                   for c in conns]
        for th in threads:
            th.start()
        lag = np.zeros(n)
        if self.single:
            self._send_singles(led, state, replied, was_sent, lag, enc,
                               due_abs, window)
        else:
            self._send_frames(led, state, replied, was_sent, lag, enc, lost,
                              due_abs, window)
        deadline = max(due_abs[-1], time.monotonic()) + self.timeout_s
        while time.monotonic() < deadline and not replied.all():
            if all(c.dead for c in conns):
                break
            time.sleep(0.005)
        state["done"] = True
        for th in threads:
            th.join(timeout=2.0)
        unanswered = np.flatnonzero(~replied)
        if len(unanswered):
            dead_conn = np.array([conns[k % len(conns)].dead
                                  for k in unanswered])
            led.fail("connection", int(dead_conn.sum()) * self.rows)
            led.fail("timeout", int((~dead_conn).sum()) * self.rows)
            if lost is not None:
                for k in unanswered:
                    lost(int(k))
        led.lags.append(lag[was_sent])
        return led

    def _send_frames(self, led, state, replied, was_sent, lag, enc, lost,
                     due_abs, window: int) -> None:
        """The open loop's sender for batch frames: frame k at its due time,
        on connection ``k % connections``. ``enc(k)`` is its bytes: made
        before the window, or by the family's session now, which the lag
        (taken after it) then holds; ``lost(k)`` (or None) tells a session
        of a frame that no reply will come for."""
        conns = self.conns
        for k in range(len(due_abs)):
            wait = due_abs[k] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            c = conns[k % len(conns)]
            with led.lock:
                full = state["inflight"] >= window
                if not full and not c.dead:
                    state["inflight"] += 1
            if full or c.dead:
                led.fail("skipped" if full else "connection", self.rows)
                replied[k] = True
            else:
                data = enc(k)
                was_sent[k] = True
                lag[k] = time.monotonic() - due_abs[k]
                if c.send(data):
                    continue
                with led.lock:
                    state["inflight"] -= 1
                    replied[k] = True
                led.fail("connection", self.rows)
            if lost is not None:
                lost(k)

    def _send_singles(self, led, state, replied, was_sent, lag, enc, due_abs,
                      window: int) -> None:
        """The open loop's sender for one-row frames, which are due faster
        than one can be sent alone: every frame that is due goes out in one
        run, a connection's share of the run (frame k on connection
        ``k % connections``, as for batch frames) in one send, and the next
        run no sooner than ``RUN_S`` later. So a frame is sent when it is
        due while frames are further apart than that, and at most ``RUN_S``
        late when they are not (the lag says which). Frames past the
        in-flight window are skipped and fail, frame by frame."""
        conns = self.conns
        n, n_conn = len(due_abs), len(conns)
        k = 0
        last_run = 0.0
        while k < n:
            now = time.monotonic()
            wait = max(due_abs[k], last_run + RUN_S) - now
            if wait > 0:
                time.sleep(wait)
                continue
            last_run = now
            j = int(np.searchsorted(due_abs, now, side="right"))
            with led.lock:
                take = min(j - k, max(0, window - state["inflight"]))
                state["inflight"] += take
            if take < j - k:
                led.fail("skipped", j - k - take)
                replied[k + take:j] = True
            end = k + take
            now = time.monotonic()
            lag[k:end] = now - due_abs[k:end]
            for ci, c in enumerate(conns):
                first = k + (ci - k) % n_conn
                if first >= end:
                    continue
                if not c.dead and c.send(enc[first:end:n_conn].tobytes()):
                    was_sent[first:end:n_conn] = True
                    continue
                lost = len(range(first, end, n_conn))
                with led.lock:
                    state["inflight"] -= lost
                    replied[first:end:n_conn] = True
                led.fail("connection", lost)
            k = j

    def _singles_back(self, led, state, replied, now: float, rsp, x0: int,
                      due_abs, cols) -> None:
        """One-row replies of the open loop, a chunk at a time."""
        k = rsp["xid"].astype(np.int64) - x0
        known = (k >= 0) & (k < len(due_abs))
        if not known.all():
            rsp, k = rsp[known], k[known]
        first = np.zeros(len(k), bool)
        first[np.unique(k, return_index=True)[1]] = True
        with led.lock:
            fresh = first & ~replied[k]
            replied[k] = True
            led.duplicates += int((~fresh).sum())
            state["inflight"] -= int(fresh.sum())
        if not fresh.all():
            rsp, k = rsp[fresh], k[fresh]
        if len(k):
            led.rows_back(now, now - due_abs[k], [col[k, 0] for col in cols],
                          rsp["status"], rsp["remaining"])

    # -- closed loop ---------------------------------------------------------
    def run_closed(self, t0: float, seconds: float) -> Ledger:
        led = Ledger(self.dep, t0, t0 + seconds)
        body = self._closed_single if self.single else self._closed_batch
        threads = [
            threading.Thread(target=body, args=(led, ci, c), daemon=True)
            for ci, c in enumerate(self.conns)
        ]
        wait = t0 - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=seconds + self.timeout_s + 10)
        return led

    def _closed_batch(self, led: Ledger, ci: int, c: Conn) -> None:
        cols = self.pool
        n_pool = len(cols[0])
        n_conn = len(self.conns)
        depth = int(self.t["outstanding"])
        x0 = self._xids_block()
        pending = {}  # xid -> (pool index, sent at)
        j = 0
        attempted = 0
        ses = self.session
        encode = (self.fam.encode_batch if ses is None
                  else functools.partial(ses.encode, ci))

        def send_one() -> bool:
            nonlocal j, attempted
            p = (ci + j * n_conn) % n_pool
            xid = x0 + j
            j += 1
            now = time.monotonic()
            if now >= led.t_end:
                return False
            pending[xid] = (p, now)
            attempted += self.rows
            return c.send(encode(xid, *[col[p] for col in cols]))

        for _ in range(depth):
            send_one()
        give_up = led.t_end + self.timeout_s
        while pending and not c.dead and time.monotonic() < give_up:
            got = c.recv()
            if not got:
                continue
            now = time.monotonic()
            for xid, rows in got[0]:
                item = pending.pop(xid, None)
                if item is None:
                    with led.lock:
                        led.duplicates += 1
                    continue
                p, at = item
                m = min(len(rows), self.rows)
                if m < self.rows:
                    led.fail("short_reply", self.rows - m)
                if ses is not None:
                    ses.back(ci, xid, [col[p] for col in cols], rows, now)
                led.rows_back(now, now - at, [col[p][:m] for col in cols],
                              rows["status"][:m], rows["remaining"][:m])
                send_one()
        with led.lock:
            led.attempted += attempted
        if pending:
            led.fail("connection" if c.dead else "timeout",
                     len(pending) * self.rows)
            if hasattr(ses, "lost"):
                for xid in pending:
                    ses.lost(ci, xid)

    def _xids_block(self) -> int:
        """A range of xids for one connection's closed loop."""
        with _XID_LOCK:
            return self._xids(20_000_000)

    def _closed_single(self, led: Ledger, ci: int, c: Conn) -> None:
        cols = [col[ci * _SINGLE_POOL:(ci + 1) * _SINGLE_POOL, 0]
                for col in self.pool]
        reqs = self.fam.encode_singles(0, *cols)
        depth = int(self.t["outstanding"])
        x0 = self._xids_block()
        sent_at = np.zeros(_SENT_RING)
        seq = 0  # requests sent so far
        back = 0  # responses seen so far

        def send(m: int) -> bool:
            nonlocal seq
            now = time.monotonic()
            if now >= led.t_end or m <= 0:
                return False
            at = np.arange(seq, seq + m)
            chunk = reqs[at % _SINGLE_POOL].copy()
            chunk["xid"] = x0 + at
            sent_at[at % _SENT_RING] = now
            seq += m
            return c.send(chunk.tobytes())

        send(depth)
        give_up = led.t_end + self.timeout_s
        while back < seq and not c.dead and time.monotonic() < give_up:
            got = c.recv()
            if not got or got[1] is None:
                continue
            now = time.monotonic()
            rsp = got[1]
            k = rsp["xid"].astype(np.int64) - x0
            known = (k >= 0) & (k < seq)
            if not known.all():
                with led.lock:
                    led.duplicates += int((~known).sum())
                rsp, k = rsp[known], k[known]
            back += len(k)
            led.rows_back(now, now - sent_at[k % _SENT_RING],
                          [col[k % _SINGLE_POOL] for col in cols],
                          rsp["status"], rsp["remaining"])
            send(len(k))
        with led.lock:
            led.attempted += seq
        if back < seq:
            led.fail("connection" if c.dead else "timeout", seq - back)

    # -- commands ------------------------------------------------------------
    def cmd_warm(self) -> dict:
        t0 = time.monotonic() + 0.05
        secs = float(self.plan["warm_seconds"])
        if self.open:
            due, cols = self.warm
            led = self.run_open(t0, due, cols,
                                int(self.t["inflight_window_frames"]), secs)
        else:
            led = self.run_closed(t0, secs)
        return led.summary()[0]

    def cmd_burst(self, rows: int) -> dict:
        """A backlog: ``rows`` rows of the mix at once (open loop), or the
        closed loop's own in-flight cap for half a second."""
        t0 = time.monotonic() + 0.02
        if self.open:
            n = max(1, rows // self.rows)
            led = self.run_open(t0, np.zeros(n), self.burst_mix.frames(n),
                                n, 0.5)
        else:
            led = self.run_closed(t0, 0.5)
        return led.summary()[0]

    def cmd_measure(self, t0: float, seconds: float, out: str) -> dict:
        # a sleeper beside the load: its gaps say whether this process was
        # held up too when the server stood still (then the whole machine did)
        gaps = []
        on = threading.Event()
        th = threading.Thread(target=tick_gaps, args=(gaps, on.is_set, t0),
                              daemon=True)
        th.start()
        try:
            summary = self._measure(t0, seconds, out)
        finally:
            on.set()
            th.join(timeout=1.0)
        summary["tick_gaps"] = gaps
        return summary

    def _measure(self, t0: float, seconds: float, out: str) -> dict:
        if self.open:
            due, cols = self.main
            led = self.run_open(t0, due, cols,
                                int(self.t["inflight_window_frames"]),
                                seconds)
        else:
            led = self.run_closed(t0, seconds)
        summary, arrays = led.summary()
        np.savez(out, **arrays)
        return summary


_XID_LOCK = threading.Lock()
TICK_S = 0.02
TICK_GAP_S = 0.1


def tick_gaps(gaps: list, stopped, t0: float, emit=None) -> None:
    """Sleep ``TICK_S`` at a time until ``stopped()``; every sleep that took
    over ``TICK_GAP_S`` goes to ``gaps`` as ``(start - t0, length)``."""
    while not stopped():
        t = time.monotonic()
        time.sleep(TICK_S)
        gap = time.monotonic() - t
        if gap > TICK_GAP_S:
            gaps.append((t - t0, gap))
            if emit is not None:
                emit(t, gap)


def witness() -> None:
    """A process that does nothing but sleep and say when it could not:
    ``{"start": monotonic seconds, "len": seconds}`` a line, until its
    standard input closes. It touches neither the server nor the load, so a
    gap here at the moment of a gap there is the machine's."""
    done = threading.Event()

    def emit(t: float, gap: float) -> None:
        print(json.dumps({"start": t, "len": gap}), flush=True)

    th = threading.Thread(target=tick_gaps, args=([], done.is_set, 0.0, emit),
                          daemon=True)
    th.start()
    print(json.dumps({"witness": True}), flush=True)
    sys.stdin.read()
    done.set()
    th.join(timeout=1.0)


def main() -> None:
    if sys.argv[1] == "witness":
        return witness()
    plan = deploy.load_json(sys.argv[1])
    gen = Generator(plan)
    print(json.dumps({"built": True, "planned_rows": gen.planned_rows()}),
          flush=True)
    deadline = time.monotonic() + float(plan.get("port_wait_s", 1100))
    while not os.path.exists(plan["port_file"]):
        if time.monotonic() > deadline:
            raise SystemExit("server port file never appeared")
        time.sleep(0.05)
    with open(plan["port_file"], encoding="utf-8") as f:
        gen.connect(int(f.read().strip()))
    print(json.dumps({"connected": len(gen.conns)}), flush=True)
    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        if words[0] == "quit":
            break
        if words[0] == "warm":
            out = gen.cmd_warm()
        elif words[0] == "burst":
            out = gen.cmd_burst(int(words[1]))
        elif words[0] == "measure":
            out = gen.cmd_measure(float(words[1]), float(words[2]), words[3])
        else:
            out = {"error": f"unknown command {words[0]!r}"}
        print(json.dumps(out), flush=True)
    for c in gen.conns:
        c.close()


if __name__ == "__main__":
    main()
