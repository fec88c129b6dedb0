"""The concurrency family: a deployment whose every rule is a cluster
concurrency rule (upstream: a cluster ``FlowRule`` of grade THREAD), asked
with BATCH_CONCURRENT_ACQUIRE frames and given back with
BATCH_CONCURRENT_RELEASE frames (codec rev 9, types 28 and 29) on the native
door's data plane. Grown from the fixture ``tests/extra/families/semaphore.py``,
which stays as it is (a fake door's frames, types 40 and 41).

Layout: ``flow.py``'s table with levels in the counts' place. Plain flows
have ids ``0 .. n_plain-1``, flow ``i`` belongs to namespace ``ns{i %
namespaces}`` and its popularity rank there is ``i // namespaces``; the
hottest ranks of every namespace carry the levels ``rules.metered_levels``
(rank 0 first), every other plain flow ``rules.unmetered_level``, which the
cell's traffic does not reach. The probe's flows have ids from
``PROBE_BASE`` up, ``len(CHECKS)`` groups of ``rules.probe.levels`` a set,
and live in the first probe namespace; no traffic touches a probe
namespace. Every rule has the timeout ``rules.resource_timeout_ms``.

A row is ``(flow_id, acquire)``, drawn by ``flow.Mix``. What makes this
family's traffic its own is the **session**: every row that comes back OK
holds a token, which the generator gives back after a hold drawn from the
seed (``hold_ms``: bands of ``share``, ``lo``, ``hi``; ``never_released_share``
of the tokens are never given back: the client died holding them, and the
server's expiry is what reclaims them). Ids whose time has come go back as
BATCH_CONCURRENT_RELEASE frames in front of their connection's next acquire
frame, at most ``MAX_IDS_PER_RELEASE`` a frame. The ledger counts acquire
rows; releases are bytes it does not count.

Wire. Type 28 request: BATCH_FLOW's rows; reply rows ``status:i8
remaining:i32 wait_ms:i32 token_id:i64``. Type 29 request: ``n:u16`` then
``token_id:i64`` a row, xid ``-1 - xid`` of the acquire frame behind it;
reply ``n:u16`` then ``status:i8`` a row, which the generator's splitter
skips and the probe reads.

The probe's checks, against ``concurrent_reference.py``, every comparison
with the limit 0 mismatches, each on flows of its own through the window's
door (a check leaves its flows empty):

    fill            level + 3 acquires in one frame, a flow of every probe
                    level: OK with distinct non-zero ids and ``remaining``
                    counting down, then BLOCKED with id 0
    release_frees   a full flow, m released: m RELEASE_OK; m + 1 more
                    acquires: m OK, 1 BLOCKED
    double_release  one id twice in a frame: RELEASE_OK once; again in a
                    later frame: ALREADY_RELEASE; ``held`` fell once
    stale_id        0, a negative id, ids never issued, an id released
                    before: ALREADY_RELEASE, and the flow's headroom is what
                    it was
    order           a full flow, ``[RELEASE k][ACQUIRE k]`` pipelined on one
                    connection: k OK
    expiry          a full flow held and nothing sent: shortly before
                    ``resource_timeout_ms`` nothing passes; after it plus
                    the stated slack the whole level passes again and the
                    old ids answer ALREADY_RELEASE
    no_rule         acquires on flow ids no rule has: NO_RULE, id 0
    mixed           seeded frames over a group's flows, acquires (one size
                    a flow) and releases interleaved over several frames,
                    stale and duplicate ids among them: every status,
                    ``remaining`` and release status equal to the
                    reference's, row for row; ids by their properties

Window invariants (``window_checks``): no acquire row answered NO_RULE, and
the tokens in the generator's hands at once, per flow, never pass the flow's
level (the session sees all connections of its process; counted there and
handed to the ledger as rows that can never be); the program's
``concurrent_table_full_total`` did not move over the window and its
``concurrent_expired_total`` did. And the window's own dispatches, which
carry releases and acquires together in one bucket under load as no probe
frame does, are held to (b), (c) and (e) by the program's state once it has
drained (``_drained``): the generators stop at the window's end and the
probe's checks leave their flows empty, so ``resource_timeout_ms`` plus the
slack after the probe began every flow's ``held`` is 0 (a release that freed
its token and did not lower ``held`` leaves it above, one that lowered
``held`` and kept the token has expiry lower it again, below), no token is
live in the table, the gauge ``concurrent_tokens_live`` reads 0, and since
the rules were loaded the tokens issued are exactly those released and those
expired (a token answered RELEASE_OK and kept is counted by expiry too).
All four with the limit 0.

Controls (``CONTROLS``), each a broken guarantee that a named check catches:
``over_admit`` (every level one higher: ``fill``), ``release_lost`` (releases
answered RELEASE_OK and dropped: ``release_frees``), ``never_expires`` (the
timeout an hour: ``expiry``).
"""

from __future__ import annotations

import heapq
import socket
import struct
import sys
import threading
import time

import numpy as np

from cellbench import wire
from cellbench.deploy import BLOCKED, DECIDED, NO_RULE, OK
from cellbench.families import concurrent_reference as reference
from cellbench.families import flow

ACQUIRE, RELEASE = 28, 29
RELEASE_OK, ALREADY_RELEASE = 6, 7
PROBE_BASE = flow.PROBE_BASE
ACQ_ROW = np.dtype([("status", "i1"), ("remaining", ">i4"), ("wait_ms", ">i4"),
                    ("token_id", ">i8")])
MAX_ROWS_PER_FRAME = wire.MAX_ROWS_PER_FRAME
MAX_IDS_PER_RELEASE = (65535 - 5 - 2) // 8  # 8191
SINGLE_REPLIES = ((), wire.SINGLE_RSP)
BATCH_REPLIES = ((ACQUIRE,), ACQ_ROW)
CHECKS = ("fill", "release_frees", "double_release", "stale_id", "order",
          "expiry", "no_rule", "mixed")
# what a run knows of the program it holds (the server process only)
_RUN = {}

Mix = flow.Mix


def encode_batch(xid: int, flow_ids, counts) -> bytes:
    """One BATCH_CONCURRENT_ACQUIRE frame: BATCH_FLOW's bytes under 28."""
    raw = bytearray(wire.encode_batch(xid, flow_ids, counts))
    raw[6] = ACQUIRE
    return bytes(raw)


def encode_release(xid: int, token_ids) -> bytes:
    ids = np.asarray(token_ids, ">i8")
    return struct.pack(">HibH", 5 + 2 + 8 * len(ids), int(xid), RELEASE,
                       len(ids)) + ids.tobytes()


def encode_singles(first_xid: int, *cols):
    raise NotImplementedError("a token is acquired in batch frames only")


class Deployment(flow.Deployment):
    def __init__(self, spec: dict):
        self.spec = spec
        self.name = spec["name"]
        r = spec["rules"]
        self.namespaces = int(r["namespaces"])
        self.n_flows = int(r["n_flows"])
        self.unmetered_level = int(r["unmetered_level"])
        self.metered_levels = [int(c) for c in r["metered_levels"]]
        # the flow family's helpers read the metered ranks from here
        self.metered_counts = self.metered_levels
        self.probe_namespaces = [int(n) for n in r["probe_namespaces"]]
        self.probe = r["probe"]
        self.resource_timeout_ms = int(r["resource_timeout_ms"])
        self.expiry_slack_ms = int(r["expiry_slack_ms"])
        self.max_tokens = int(spec["max_tokens"])
        self.window_ms = (int(spec["engine"]["bucket_ms"])
                          * int(spec["engine"]["n_buckets"]))
        self.bucket_ms = int(spec["engine"]["bucket_ms"])
        self.probe_rules = self._probe_rules()
        self.n_plain = self.n_flows - len(self.probe_rules)
        if self.n_plain < self.namespaces * (len(self.metered_levels) + 1):
            raise ValueError("too few plain flows for the metered ranks")

    def level_of(self, flow_ids) -> np.ndarray:
        """The level of plain flows, by their rank."""
        rank = np.asarray(flow_ids, np.int64) // self.namespaces
        lv = np.asarray(self.metered_levels + [self.unmetered_level])
        return lv[np.minimum(rank, len(self.metered_levels))]

    # -- the ledger's view of a row ------------------------------------------
    def ledger_counts(self) -> np.ndarray:
        """No key: a level bounds tokens held at once, not tokens admitted
        per window, and is held by the session's count (``window_checks``)."""
        return np.zeros(0)

    def ledger_view(self, cols, st, remaining):
        """Every verdict of the step is a decision (FAIL, a full token
        table, is not, and fails its row). Rows that can never be: NO_RULE
        (every flow of the mix has a rule), and what the generator's
        session counted since the last call: tokens in its hands past a
        flow's level."""
        ses = getattr(self, "session", None)
        never = int((st == NO_RULE).sum()) + (ses.take_over() if ses else 0)
        none = np.zeros(0, np.int64)
        return DECIDED[st], np.zeros(len(st), bool), never, none, none

    def window_checks(self, client: dict) -> list:
        checks = [("acquire rows NO_RULE or tokens in hand past a level",
                   client["never_rows"], 0)]
        moved = _counters_moved()
        if moved is not None:
            checks += [
                ("concurrent_table_full_total over the window",
                 moved["concurrent_table_full_total"], 0),
                ("windows in which no token expired",
                 int(moved["concurrent_expired_total"] <= 0), 0),
            ] + _drained(self)
        return checks

    # -- rules -----------------------------------------------------------------
    def _probe_rules(self) -> list:
        """``(flow_id, level, check, set)`` of the probe's own flows: a
        group of the probe levels for every check of every set."""
        out = []
        fid = PROBE_BASE
        for k in range(int(self.probe["sets"])):
            for check in CHECKS:
                for level in self.probe["levels"]:
                    out.append((fid, int(level), check, k))
                    fid += 1
        return out

    def probe_set(self, k: int) -> dict:
        """``{check: [(flow id, level), ...]}`` of set ``k``."""
        out = {}
        for fid, level, check, s in self.probe_rules:
            if s == k:
                out.setdefault(check, []).append((fid, level))
        return out

    def rules(self):
        """Every rule as ``(flow_id, level, namespace_name)``."""
        nm = len(self.metered_levels)
        for i in range(self.n_plain):
            rank = i // self.namespaces
            yield (i, self.metered_levels[rank] if rank < nm
                   else self.unmetered_level, f"ns{i % self.namespaces}")
        ns = f"ns{self.probe_namespaces[0]}"
        for fid, level, _check, _set in self.probe_rules:
            yield fid, level, ns


Deployment.family = sys.modules[__name__]


# -- the generator's side: the session ----------------------------------------
class _Held:
    """The tokens one reply brought: ids, flows, counts, the time from which
    each goes back (``inf``: never), which have gone back, and which still
    count as in hand."""

    __slots__ = ("ids", "flows", "cnts", "free", "sent", "counted",
                 "forget_at")

    def __init__(self, ids, flows, cnts, free, forget_at):
        self.ids, self.flows, self.cnts, self.free = ids, flows, cnts, free
        self.sent = np.zeros(len(ids), bool)
        self.counted = np.ones(len(ids), bool)
        self.forget_at = forget_at


class Session:
    """Per connection, the tokens of the rows that came back OK, each with
    the time from which it goes back: the reply's time plus a hold drawn
    from the seed, by connection, in the order the connection's replies
    came. ``encode`` puts every id whose time has come in front of the
    connection's next acquire frame. A share of the tokens is never given
    back. A lost frame's ids never reached the session.

    The count that holds the level: ``in_hand[flow]`` rises when a reply
    brings tokens and falls when their release is about to be sent, so it
    is never above what the server holds for this process; and whatever a
    reply brought leaves the count once ``resource_timeout_ms`` (less the
    clocks' grain) have passed since its frame was *sent*, given back or
    not: from then on the server may have reclaimed it. A reply that lifts
    the count past the flow's level is counted (``take_over``:
    ``Deployment.ledger_view`` hands it to the ledger)."""

    CLOCK_GRAIN_S = 0.002  # the server's clock counts whole milliseconds

    def __init__(self, tr: dict, dep, seed: int, proc: int,
                 n_connections: int):
        bands = tr["hold_ms"]
        share = np.asarray([b["share"] for b in bands], np.float64)
        self.band_cdf = np.cumsum(share / share.sum())
        self.band_lo = np.asarray([b["lo"] for b in bands]) / 1000.0
        self.band_hi = np.asarray([b["hi"] for b in bands]) / 1000.0
        self.never = float(tr.get("never_released_share", 0.0))
        self.timeout_s = dep.resource_timeout_ms / 1000.0 - self.CLOCK_GRAIN_S
        self.dep = dep
        self.rng = [np.random.default_rng([int(seed), int(proc), ci, 4099])
                    for ci in range(n_connections)]
        self.locks = [threading.Lock() for _ in range(n_connections)]
        self.held = [[] for _ in range(n_connections)]  # _Held, to give back
        self.sent_at = [{} for _ in range(n_connections)]
        # all connections: what is in hand, and the replies' tokens in the
        # order they are to be forgotten
        self.hand_lock = threading.Lock()
        self.in_hand = np.zeros(dep.n_plain, np.int64)
        self.fresh = []  # a heap of (forget at, arrival, _Held)
        self.arrivals = 0
        self.over = 0
        # what the tests read
        self.released = 0
        self.abandoned = 0
        self.lost_xids = []
        dep.session = self

    def take_over(self) -> int:
        with self.hand_lock:
            over, self.over = self.over, 0
        return over

    def _forget(self, now: float) -> None:
        """With ``hand_lock`` held: replies whose time has come leave the
        count, with whatever of theirs was still in it."""
        while self.fresh and self.fresh[0][0] <= now:
            h = heapq.heappop(self.fresh)[2]
            if h.counted.any():
                np.subtract.at(self.in_hand, h.flows[h.counted],
                               h.cnts[h.counted])
                self.abandoned += int((h.counted & np.isinf(h.free)).sum())
                h.counted[:] = False

    def encode(self, ci: int, xid: int, flow_ids, counts) -> bytes:
        now = time.monotonic()
        due = []
        with self.locks[ci]:
            self.sent_at[ci][xid] = now
            later = []
            for h in self.held[ci]:
                go = ~h.sent & (h.free <= now)
                if go.any():
                    due.append((h, go))
                    h.sent |= go
                if not (h.sent | np.isinf(h.free)).all():
                    later.append(h)
            self.held[ci] = later
        out = b""
        if due:
            with self.hand_lock:
                for h, go in due:
                    down = go & h.counted
                    np.subtract.at(self.in_hand, h.flows[down], h.cnts[down])
                    h.counted &= ~go
            ids = np.concatenate([h.ids[go] for h, go in due])
            self.released += len(ids)
            for k in range(0, len(ids), MAX_IDS_PER_RELEASE):
                out += encode_release(-1 - xid,
                                      ids[k:k + MAX_IDS_PER_RELEASE])
        return out + encode_batch(xid, flow_ids, counts)

    def back(self, ci: int, xid: int, cols, reply_rows, t: float) -> None:
        with self.locks[ci]:
            sent = self.sent_at[ci].pop(xid, t)
        ok = reply_rows["status"] == OK
        n = int(ok.sum())
        if not n:
            return
        ids = reply_rows["token_id"][ok].astype(np.int64)
        flows = np.asarray(cols[0])[:len(ok)][ok].astype(np.int64)
        cnts = np.asarray(cols[1])[:len(ok)][ok].astype(np.int64)
        rng = self.rng[ci]
        band = np.minimum(np.searchsorted(self.band_cdf, rng.random(n)),
                          len(self.band_cdf) - 1)
        hold = self.band_lo[band] + rng.random(n) * (
            self.band_hi[band] - self.band_lo[band])
        free = np.where(rng.random(n) < self.never, np.inf, t + hold)
        h = _Held(ids, flows, cnts, free, sent + self.timeout_s)
        with self.hand_lock:
            self._forget(time.monotonic())
            # (a heap: replies of different connections come out of the
            # order their frames were sent in)
            self.arrivals += 1
            heapq.heappush(self.fresh, (h.forget_at, self.arrivals, h))
            np.add.at(self.in_hand, flows, cnts)
            uf = np.unique(flows)
            self.over += int((self.in_hand[uf] > self.dep.level_of(uf)).sum())
        if not np.isinf(free).all():
            with self.locks[ci]:
                self.held[ci].append(h)

    def lost(self, ci: int, xid: int) -> None:
        with self.locks[ci]:
            self.sent_at[ci].pop(xid, None)
        self.lost_xids.append(int(xid))


# -- the program's side ---------------------------------------------------------
def service_args(dep) -> dict:
    """The token table's size. A program from before PR 41 serves
    concurrency limiting from a Python dict on the control lane and has no
    batch frames for it: said here, before anything is built, so that such a
    tree fails at once and cleanly."""
    from sentinel_tpu.cluster.token_service import DefaultTokenService

    if not hasattr(DefaultTokenService, "dispatch_concurrent_batch"):
        raise SystemExit(
            "this program has no concurrency lane (DefaultTokenService."
            "dispatch_concurrent_batch, BATCH_CONCURRENT_ACQUIRE / _RELEASE, "
            "codec rev 9): the concurrent family cannot run on it")
    return {"concurrent_max_tokens": dep.max_tokens}


def _rules(dep, level_plus: int = 0, timeout_ms=None) -> list:
    from sentinel_tpu.cluster.concurrent import ConcurrentFlowRule
    from sentinel_tpu.engine.rules import ThresholdMode

    timeout = dep.resource_timeout_ms if timeout_ms is None else timeout_ms
    return [ConcurrentFlowRule(fid, level + level_plus, ThresholdMode.GLOBAL,
                               timeout, ns)
            for fid, level, ns in dep.rules()]


def load_rules(service, dep) -> int:
    rules = _rules(dep)
    service.load_concurrent_rules(rules)
    n_rules = len(service.current_concurrent_rules())
    if n_rules != dep.n_flows:
        raise RuntimeError(f"{n_rules} concurrency rules loaded, "
                           f"{dep.n_flows} in the file")
    _RUN.update(service=service, dep=dep, probe_no_rule=0, probe_t0=None)
    _RUN["loaded"] = _totals()  # a new plane: no token issued yet
    return n_rules


def drive_before_window(built, tr: dict, dep, seed: int, compiles: list,
                        say) -> list:
    """In process, before the window: at every serve bucket one dispatch of
    the mix's own rows and, behind it, one that gives their tokens back
    (``warmup()`` compiled the steps on a throwaway plane; this runs each
    once on the live one). Nothing fuses on this lane."""
    mix = Mix(tr, dep, seed, 991)
    service = built.service
    for bucket in dep.spec["serve_buckets"]:
        n = int(bucket)
        ids, acq = (c.reshape(-1)[:n]
                    for c in mix.frames(-(-n // mix.frame_rows)))
        n0 = len(compiles)
        status, _rm, _wt, tokens = service.request_concurrent_batch(ids, acq)
        mine = tokens[status == OK]
        back = service.request_concurrent_batch(
            mine, None, np.ones(len(mine), bool))[0]
        say(f"warm-up: {n} acquires in process (bucket {bucket}), "
            f"{len(mine)} OK, {int((back == RELEASE_OK).sum())} given back, "
            f"{len(compiles) - n0} compiles")
    return []


def _totals():
    if _RUN.get("service") is None:
        return None
    from sentinel_tpu.metrics.server import server_metrics

    return server_metrics().concurrent_totals()


def _counters_moved():
    """The program's concurrency counters since the window's start (the
    last call of ``progress``), or None where this process holds no
    server."""
    now, base = _totals(), _RUN.get("base")
    if now is None or base is None:
        return None
    return {k: now[k] - base.get(k, 0) for k in now}


def _settled_totals(limit_s: float = 2.0) -> dict:
    """The counters once the reply lanes have counted what they answered
    (they answer first and count after): two readings 50 ms apart in which
    no dispatch was counted."""
    end = time.monotonic() + limit_s
    now = _totals()
    while time.monotonic() < end:
        time.sleep(0.05)
        again = _totals()
        if again["concurrent_dispatch_total"] == now[
                "concurrent_dispatch_total"]:
            return again
        now = again
    return now


def _drained(dep) -> list:
    """``[(what, got, 0)]`` of the program's own state after the probe:
    every token of the window released or expired (the module's docstring).
    Called in the server's process, after the probe (``run_cell`` probes,
    then reads the window's invariants); it waits out what is left of
    ``resource_timeout_ms`` plus the slack since the probe began, which is
    nothing where the probe's expiry check ran."""
    service, t0 = _RUN["service"], _RUN.get("probe_t0")
    if t0 is not None:
        due = t0 + (dep.resource_timeout_ms + dep.expiry_slack_ms) / 1e3 + 0.05
        time.sleep(max(0.0, due - time.monotonic()))
    now, base = _settled_totals(), _RUN["loaded"]
    moved = {k: now[k] - base.get(k, 0) for k in now}
    snap = service.concurrent_stats()
    issued = (moved["concurrent_acquire_rows_total"]
              - moved["concurrent_blocked_total"]
              - moved["concurrent_table_full_total"]
              - _RUN.get("probe_no_rule", 0))
    gone = (moved["concurrent_release_rows_total"]
            - moved["concurrent_already_release_total"]
            + moved["concurrent_expired_total"])
    return [
        ("flows whose held is not 0 once the window has drained",
         sum(1 for h in snap["held"].values() if h), 0),
        ("tokens live in the table once the window has drained",
         len(snap["tokens"]), 0),
        ("concurrent_tokens_live once the window has drained",
         abs(now["concurrent_tokens_live"]), 0),
        ("tokens issued less released less expired since the rules loaded",
         abs(issued - gone), 0),
    ]


def progress(built):
    """What the stall watch expects to keep rising: dispatches whose
    verdicts the reply lanes have materialized. Called as a window is about
    to start: the counters' reading here is what the window's are held
    against."""
    _RUN["base"] = _totals()
    return flow.progress(built)


# -- the probe's sets -----------------------------------------------------------
class _Wire:
    """One connection to the door, for checks that read token ids and
    release statuses: ``ask`` sends frames (pipelined, in order) and returns
    their replies in the order they were sent."""

    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=10)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.settimeout(20.0)
        self.buf = bytearray()
        self.xid = 1_950_000_000

    def close(self) -> None:
        self.sock.close()

    def _frame(self):
        while True:
            if len(self.buf) >= 2:
                flen = (self.buf[0] << 8) | self.buf[1]
                if len(self.buf) >= 2 + flen:
                    body = bytes(self.buf[2:2 + flen])
                    del self.buf[:2 + flen]
                    return body
            try:
                data = self.sock.recv(1 << 18)
            except socket.timeout as e:
                raise RuntimeError("the probe's reply timed out") from e
            if not data:
                raise RuntimeError("probe connection closed by the server")
            self.buf += data

    def ask(self, frames):
        """``frames``: ``("acquire", flow ids, counts)`` or ``("release",
        ids)``. Returns a list of replies: for an acquire ``(status,
        remaining, token ids)``, for a release its statuses."""
        raw, xids = b"", []
        for f in frames:
            self.xid += 1
            xids.append(self.xid)
            if f[0] == "acquire":
                raw += encode_batch(self.xid, np.asarray(f[1], np.int64),
                                    np.asarray(f[2], np.int32))
            else:
                raw += encode_release(self.xid, f[1])
        self.sock.sendall(raw)
        got = {}
        while len(got) < len(frames):
            body = self._frame()
            xid, mtype = struct.unpack_from(">ib", body, 0)
            if xid not in xids:
                continue  # a push, a ping
            n = struct.unpack_from(">H", body, 5)[0]
            if mtype == RELEASE:
                got[xid] = np.frombuffer(body, np.int8, n, 7).copy()
            else:
                rows = np.frombuffer(body, ACQ_ROW, n, 7)
                got[xid] = (rows["status"].astype(np.int8),
                            rows["remaining"].astype(np.int32),
                            rows["token_id"].astype(np.int64))
        return [got[x] for x in xids]


class _Checks:
    def __init__(self, p):
        self.p, self.dep, self.rng = p, p.dep, p.rng
        self.flows = p.dep.probe_set(p.probe_set)
        self.t0 = time.monotonic()
        self.seen = set()  # every id a check was handed: never twice

    def _now(self) -> int:
        return 10_000 + int((time.monotonic() - self.t0) * 1000)

    def _ref(self, check: str):
        return reference.Reference(dict(self.flows[check]),
                                   self.dep.resource_timeout_ms)

    def _ids_bad(self, status, tokens) -> int:
        """Rows whose token id breaks (a)'s properties: an OK row's id is
        non-zero and new, any other row's is 0."""
        bad = 0
        for st, tok in zip(status.tolist(), tokens.tolist()):
            if st == OK:
                bad += tok == 0 or tok in self.seen
                self.seen.add(tok)
            else:
                bad += tok != 0
        return bad

    def _acquire(self, w, ref, flows, counts=None):
        """One acquire frame to the door and to ``ref``: ``(mismatched
        rows, token ids, the reference's ids, status)``."""
        flows = np.asarray(flows, np.int64)
        counts = (np.ones(len(flows), np.int32) if counts is None
                  else np.asarray(counts, np.int32))
        (status, remaining, tokens), = w.ask([("acquire", flows, counts)])
        # (rows that took no token and are no BLOCKED: ``_drained`` counts)
        _RUN["probe_no_rule"] = _RUN.get("probe_no_rule", 0) + int(
            (status == NO_RULE).sum())
        want_s, want_r, want_t = ref.acquire_frame(self._now(), flows, counts)
        bad = int(((status != np.asarray(want_s, np.int8))
                   | (remaining != np.asarray(want_r, np.int32))).sum())
        return bad + self._ids_bad(status, tokens), tokens, want_t, status

    def _release(self, w, ref, tokens, ref_tokens):
        """One release frame; ``ref_tokens`` are the reference's ids of the
        same tokens (its ids are its own)."""
        (status,) = w.ask([("release", np.asarray(tokens, np.int64))])
        want = ref.release_frame(ref_tokens)
        return int((status != np.asarray(want, np.int8)).sum())

    def _run(self, name: str, body) -> None:
        w = _Wire(self.p.port)
        t = time.monotonic()
        try:
            rows, bad = body(w, self._ref(name))
        finally:
            w.close()
        self.p.record(name, rows, bad, time.monotonic() - t)

    # -- the checks ------------------------------------------------------------
    def fill(self) -> None:
        def body(w, ref):
            rows = bad = 0
            for fid, level in self.flows["fill"]:
                n = level + 3
                b, tokens, ref_tokens, status = self._acquire(
                    w, ref, np.full(n, fid))
                bad += b + int((status[:level] != OK).sum()) + int(
                    (status[level:] != BLOCKED).sum())
                bad += self._release(w, ref, tokens[:level],
                                     ref_tokens[:level])
                rows += n + level
            return rows, bad
        self._run("fill", body)

    def release_frees(self) -> None:
        def body(w, ref):
            fid, level = self.flows["release_frees"][1]
            m = max(1, level // 3)
            bad, tokens, ref_tokens, _s = self._acquire(
                w, ref, np.full(level, fid))
            bad += self._release(w, ref, tokens[:m], ref_tokens[:m])
            b, more, ref_more, status = self._acquire(
                w, ref, np.full(m + 1, fid))
            bad += b + int((status[:m] != OK).sum()) + int(status[m] != BLOCKED)
            bad += self._release(
                w, ref, np.concatenate([tokens[m:], more[:m]]),
                list(ref_tokens[m:]) + list(ref_more[:m]))
            return 2 * level + 2 * m + 1, bad
        self._run("release_frees", body)

    def double_release(self) -> None:
        def body(w, ref):
            fid, level = self.flows["double_release"][0]
            bad, tokens, ref_tokens, _s = self._acquire(
                w, ref, np.full(level, fid))
            bad += self._release(w, ref, [tokens[0], tokens[0]],
                                 [ref_tokens[0], ref_tokens[0]])
            bad += self._release(w, ref, [tokens[0]], [ref_tokens[0]])
            # held fell once: one more passes, a second does not
            b, more, ref_more, status = self._acquire(w, ref, [fid, fid])
            bad += b + int(status[0] != OK) + int(status[1] != BLOCKED)
            bad += self._release(w, ref, list(tokens[1:]) + [more[0]],
                                 list(ref_tokens[1:]) + [ref_more[0]])
            return 2 * level + 5, bad
        self._run("double_release", body)

    def stale_id(self) -> None:
        def body(w, ref):
            fid, level = self.flows["stale_id"][1]
            bad, tokens, ref_tokens, _s = self._acquire(
                w, ref, np.full(level, fid))
            bad += self._release(w, ref, tokens[:1], ref_tokens[:1])
            # 0, a negative id, ids far past any this server has issued (a
            # later generation of a live slot among them), one released
            far = int(self.dep.max_tokens) * 1_000_003
            stale = [0, -7, int(tokens[1]) + far, far + 5, 2**62 + 11,
                     int(tokens[0])]
            bad += self._release(w, ref, stale, [0, 0, 0, 0, 0, ref_tokens[0]])
            # nothing changed: one slot free (the one released), not two
            b, more, ref_more, status = self._acquire(w, ref, [fid, fid])
            bad += b + int(status[0] != OK) + int(status[1] != BLOCKED)
            bad += self._release(w, ref, list(tokens[1:]) + [more[0]],
                                 list(ref_tokens[1:]) + [ref_more[0]])
            return 2 * level + len(stale) + 3, bad
        self._run("stale_id", body)

    def order(self) -> None:
        def body(w, ref):
            fid, level = self.flows["order"][1]
            k = max(1, level // 2)
            bad, tokens, ref_tokens, _s = self._acquire(
                w, ref, np.full(level, fid))
            # one send: the release frame, the acquire frame behind it
            flows = np.full(k, fid, np.int64)
            ones = np.ones(k, np.int32)
            rel, (status, remaining, more) = w.ask(
                [("release", tokens[:k]), ("acquire", flows, ones)])
            want_rel = ref.release_frame(ref_tokens[:k])
            want_s, want_r, ref_more = ref.acquire_frame(self._now(), flows,
                                                         ones)
            bad += int((rel != np.asarray(want_rel, np.int8)).sum())
            bad += int(((status != np.asarray(want_s, np.int8))
                        | (remaining != np.asarray(want_r, np.int32))).sum())
            bad += int((status != OK).sum()) + self._ids_bad(status, more)
            bad += self._release(w, ref, np.concatenate([tokens[k:], more]),
                                 list(ref_tokens[k:]) + list(ref_more))
            return 2 * level + 2 * k, bad
        self._run("order", body)

    def expiry(self) -> None:
        def body(w, ref):
            fid, level = self.flows["expiry"][2]
            timeout = self.dep.resource_timeout_ms / 1000.0
            slack = self.dep.expiry_slack_ms / 1000.0
            t_sent = time.monotonic()
            bad, tokens, ref_tokens, _s = self._acquire(
                w, ref, np.full(level, fid))
            t_back = time.monotonic()
            # shortly before the timeout (counted from the send: the server
            # issued no earlier) nothing passes
            time.sleep(max(0.0, t_sent + timeout - 0.25 - time.monotonic()))
            early = time.monotonic() < t_sent + timeout - 0.05
            b, _t, _rt, status = self._acquire(w, ref, [fid])
            if early:
                bad += b + int(status[0] != BLOCKED)
            # after the timeout and the stated slack (counted from the
            # reply: the server issued no later) the whole level passes
            time.sleep(max(0.0, t_back + timeout + slack + 0.05
                           - time.monotonic()))
            ref.expire(self._now())
            b, fresh, ref_fresh, status = self._acquire(
                w, ref, np.full(level, fid))
            bad += b + int((status != OK).sum())
            bad += self._release(w, ref, tokens, ref_tokens)  # all expired
            bad += self._release(w, ref, fresh, ref_fresh)
            return 4 * level + 1, bad
        self._run("expiry", body)

    def no_rule(self) -> None:
        def body(w, ref):
            ids = PROBE_BASE + 500_000 + self.rng.integers(0, 1000, 16)
            bad, _t, _rt, status = self._acquire(w, ref, ids)
            return len(ids), bad + int((status != NO_RULE).sum())
        self._run("no_rule", body)

    def mixed(self) -> None:
        def body(w, ref):
            group = self.flows["mixed"]
            fids = np.asarray([f for f, _l in group], np.int64)
            size = {int(f): 1 + k % 3 for k, f in enumerate(fids)}
            mine, theirs = [], []  # live ids: the door's, the reference's
            rows = bad = 0
            for _frame in range(12):
                n = int(self.rng.integers(8, 40))
                flows = self.rng.choice(fids, n)
                counts = np.asarray([size[int(f)] for f in flows], np.int32)
                b, tokens, ref_tokens, status = self._acquire(
                    w, ref, flows, counts)
                bad += b
                ok = status == OK
                mine += tokens[ok].tolist()
                theirs += [t for t, o in zip(ref_tokens, ok) if o]
                rows += n
                # give some back, one of them twice, a stale id among them
                m = int(self.rng.integers(0, len(mine) + 1))
                at = self.rng.permutation(len(mine))[:m].tolist()
                ids = [mine[i] for i in at] + [0]
                ref_ids = [theirs[i] for i in at] + [0]
                if at:
                    ids.append(mine[at[0]])
                    ref_ids.append(theirs[at[0]])
                bad += self._release(w, ref, ids, ref_ids)
                rows += len(ids)
                for i in sorted(at, reverse=True):
                    del mine[i], theirs[i]
            bad += self._release(w, ref, mine, theirs)
            ref.check()
            return rows + len(mine), bad
        self._run("mixed", body)


def probe_checks(p) -> list:
    _RUN["probe_t0"] = time.monotonic()  # the generators have stopped
    c = _Checks(p)
    return [getattr(c, name) for name in CHECKS]


# -- the controls ---------------------------------------------------------------
def over_admit(service):
    """Every level one higher than the file's: one call past the level.
    (``server.build`` hands a control the service alone, once the rules are
    loaded; the deployment is the one ``load_rules`` noted.)"""
    service.load_concurrent_rules(_rules(_RUN["dep"], level_plus=1))
    return service


def never_expires(service):
    """The resource timeout an hour: a dead client's tokens are kept."""
    service.load_concurrent_rules(_rules(_RUN["dep"], timeout_ms=3_600_000))
    return service


class ReleaseLost:
    """The service with its release rows answered RELEASE_OK and dropped."""

    def __init__(self, service):
        self._service = service

    def __getattr__(self, name):
        return getattr(self._service, name)

    def dispatch_concurrent_batch(self, ids, counts=None, is_release=None):
        ids = np.array(ids, np.int64)  # a copy: the door reuses its blocks
        n = len(ids)
        rel = (np.zeros(n, bool) if is_release is None
               else np.array(is_release, bool))
        keep = np.flatnonzero(~rel)
        mat = self._service.dispatch_concurrent_batch(
            ids[keep], None if counts is None else np.array(counts)[keep],
            np.zeros(len(keep), bool))

        def altered():
            st, rm, wt, tok = mat()
            status = np.full(n, RELEASE_OK, np.int8)
            remaining, wait = np.zeros(n, np.int32), np.zeros(n, np.int32)
            tokens = np.zeros(n, np.int64)
            status[keep], remaining[keep], wait[keep], tokens[keep] = (
                st, rm, wt, tok)
            return status, remaining, wait, tokens
        return altered

    def request_concurrent_batch(self, ids, counts=None, is_release=None):
        return self.dispatch_concurrent_batch(ids, counts, is_release)()


CONTROLS = {"over_admit": over_admit, "release_lost": ReleaseLost,
            "never_expires": never_expires}
