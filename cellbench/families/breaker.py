"""The breaker family: a flow table whose hot flows carry circuit breakers
(``DegradeRule``: SLOW_REQUEST_RATIO, ERROR_RATIO, ERROR_COUNT) and whose
clients report how their calls went: BATCH_FLOW request frames as the flow
family's, each preceded in the same send by one OUTCOME_REPORT frame.
``families/__init__.py`` lists what a family owns; everything a flow table
shares (the layout of ids and namespaces, the tenants and flows of a mix, the
door, the fused depths, the counts of the metered ranks) is ``flow.py``'s and
is used from there.

**The one departure from upstream.** A generator encodes its frames before the
server is up and cannot know a verdict, so *every row on a guarded flow
completes and is reported, DEGRADED or not*; a real client reports admitted
calls only. That inflates the outcome rows by about the DEGRADED share and
lets a row that was no probe resolve a HALF_OPEN probe; the device's work per
report and per transition is a deployment's. The plain reference is given the
same inputs. A traffic file that states ``"reports": "admitted"`` reports as
upstream does (``AdmittedReports``, below): its frames are encoded at send
time by a session (``families/__init__.py``), and the report in front of a
connection's frame holds the completions of the rows of that connection's
earlier frames whose verdicts have come back OK or SHOULD_WAIT, with the
outcome the health script gave them; a DEGRADED, BLOCKED or failed row
completes nothing. Without the key (the benchmark's cell) nothing changes.

Layout. As ``flow.py``: plain flow ``i`` belongs to namespace ``ns{i %
namespaces}`` with popularity rank ``i // namespaces``; the hottest ranks are
metered at ``rules.metered_counts``. The ``rules.guarded_ranks`` hottest
ranks of every *traffic* namespace carry a breaker: rank ``r`` has strategy
``r % 3`` (SLOW_REQUEST_RATIO, ERROR_RATIO, ERROR_COUNT, with the thresholds
of ``rules.degrade``), every breaker the file's ``min_request_amount``,
``stat_interval_ms`` and ``recovery_timeout_ms``. The probe's flows live in
the first probe namespace, from ``PROBE_BASE`` up.

A row is ``(flow_id, acquire, rt_ms, exception)``; ``rt_ms < 0`` means the row
completes nothing (a row of an unguarded flow). A generator carries rows as
seven columns ``(ids, acq, rt, exc, told_ids, told_rt, told_exc)``: the last
three are the first, third and fourth of the *previous frame of the same
connection* (``loadgen`` sends frame ``k`` on connection ``k % connections``),
which is what the frame's report holds: the product client's coalescing
(``cluster/client.py``: buffered completions go out as one OUTCOME_REPORT
before the next request frame). ``encode_batch`` returns the report (type 21,
``n:u16`` then ``flow_id:i64 rt_ms:i32 exc:u8`` a row: this module's own copy
of the layout; xid ``-1 - xid``, outside every range replies are paired by)
followed by the BATCH_FLOW frame. The server answers no report, and
``wire.Splitter`` skips any type the family does not name.

Mix parameters: the flow family's, plus ``reports`` (``"admitted"``: above;
absent: every guarded row) and ``health``, the script of the
dependencies behind the guarded flows. It is **the file's, keyed on
(namespace, rank), never on the seed**; the seed draws which rows a frame
holds and the per-row draws inside a phase.

    health  {"period_s": 5, "sick_s": {"even": 1, "odd": 3}, "slot_s": 0.25,
             "healthy": {"rt_ms": [5, 30]},
             "sick": {"slow_share": 0.9, "slow_rt_ms": [80, 400],
                      "exc_share": 0.8},
             "trip_delay_s": {...}, "expect": {...}}

A guarded flow's dependency is sick for ``sick_s`` seconds (by its rank's
parity) in every ``period_s``, from its *phase* on; while sick a
SLOW_REQUEST_RATIO flow's completions are slow for ``slow_share`` of its
rows, the others' throw for ``exc_share``. The phases (``stagger``) are set,
from the mix's own popularities alone, so that in every ``slot_s`` of the
schedule the traffic-weighted share of sick dependencies, and of breakers
expected OPEN, is level: the hottest tenant's flows are placed first, then
every other by weight, each where it leaves both loads lowest. The script
runs on the schedule's clock (frame ``k`` is due ``k * frame_rows / rate``
seconds in; ``traffic.open_schedule``), through the warm-up and the window
alike, each from its own frame 0 (``start_s``, absent from the benchmark's
file, moves that start inside the period: the tests' window offsets). Bursts,
pools and what is driven in process report healthy completions only (a
backlog is not a time line).

The ledger. Keys ``0 .. M-1`` as the flow family's (tokens admitted on the
metered ranks, limit the count). Every guarded slot (namespace x guarded
rank) has two more keys, which count rows by 100 ms bin of reply time: rows
that came *through* the breaker (OK or BLOCKED) and rows DEGRADED. Counts of
a window, limit 0 each (``window_checks``):

    unmetered rows BLOCKED; rows DEGRADED on a flow without a breaker
    a guarded flow with rows through in a bin wholly inside an OPEN span. A
        span is known from its first DEGRADED bin ``b`` (the three bins
        before it hold no DEGRADED row and at least one row through): the
        breaker opened no later than that reply and no earlier than one
        reply latency before it, so the bins from ``b + 2`` to two bins
        before ``recovery_timeout_ms`` after ``b`` lie inside it. The slack
        is the skew of reply times between lanes; a reply slower than a bin
        (``lat_max`` of the span's bins) widens it by as many bins
and one with a limit of its own: the DEGRADED share of the window's rows on
guarded flows of even rank, and of odd, each within ``expect.tolerance`` of
what the script and the reference give (``expect.degraded_share``; compared
in thousandths). Breakers that never trip, never recover or recover at once
fail this over a parity's flows together. One stuck breaker among hundreds
does not, so the hottest tenant's guarded flows are held as well, one check
a flow (``expect.hottest_flows``: by parity of rank, the band a flow's share
of a window of two periods or more has to lie in; read as the distance from
the band's middle against its half width). A band is what the script and the
plain reference give for those flows over 50 seeds (even 0.31-0.50, mean
0.40; odd 0.63-0.90, mean 0.80: ``cellbench/tests/test_breaker.py``) with a
tenth of room at either end, and stays clear of 0 and 1: a breaker that
never trips, never recovers, or closes at every first probe where the
reference rolls back (an odd flow reading like an even one) is outside. Only
that tenant's flows: a frame is one tenant's, so a flow sees its rows in
bursts of its tenant's frames (16 a second for the hottest tenant, one in
4 s for the coldest, at times the seed permutes), and a colder flow's share
swings by tenths with them or never trips at all.

The probe's checks, every status and every ``remaining`` (the retry-after of
a DEGRADED row) against ``breaker_reference.py``, limit 0 mismatches.
``tight``, ``big``, ``guard`` and ``paced`` are the flow family's, with this
family's frames. Reports and requests travel different lanes of the server
with no order between them, so a check waits on the server's own count of
completion rows ingested (``outcome_step_rows_total``, in process) before it
asks; each check has flows of its own:

    trip_ratio  ERROR_RATIO 0.5: 5 of 10 failed is not over it (OK), 6 of 10
                is (DEGRADED, retry-after the whole timeout); 4 of 4 is under
                ``min_request_amount``, 3 of 5 is at it; 257 of 513 trips (a
                count past 256, which 8-bit totals get wrong)
    trip_slow   SLOW_REQUEST_RATIO 0.6 over 50 ms: 6 of 10 slow is not over
                it, 7 of 10 is; 4 of 4 and 5 of 5; an ``rt_ms`` of exactly
                50 is not slow
    trip_count  ERROR_COUNT 4: 4 exceptions pass, 5 trip
    open_holds  a tripped flow answers DEGRADED in every row until the
                timeout, its retry-after what is left of it
    probe_one   after the timeout one 64-row frame on the flow: exactly the
                first row in frame order is admitted, 63 are DEGRADED
    recover     the probe's report is healthy: the next frame passes whole
    rollback    the probe's report is sick: OPEN again, for the whole timeout
    fence       bad completions reported while OPEN do not trip the flow
                again once a healthy probe has closed it

Controls: ``over_admit`` as the flow family's; ``unguarded``, the degrade
rules taken out of the loaded table (the trip checks catch it); and
``reference_8bit``, the sound server held against the reference with 8-bit
totals (``big`` and ``trip_ratio`` catch it).
"""

from __future__ import annotations

import functools
import sys
import threading
import time

import numpy as np

from cellbench import probe, traffic, wire
from cellbench.deploy import (BLOCKED, DECIDED, DEFAULT, DEGRADED, OK,
                              RATE_LIMITER, SHOULD_WAIT)
from cellbench.families import breaker_reference, flow

PROBE_BASE = flow.PROBE_BASE
OUTCOME_REPORT = 21  # the wire's type byte of a completion report
OUTCOME_ROW = np.dtype([("flow_id", ">i8"), ("rt_ms", ">i4"), ("exc", "u1")])
BIN_S = 0.1  # loadgen.BIN_S: the ledger's bins of reply time
RETRY_SLACK_MS = 150  # client's clock against the server's, for a time left
INGEST_WAIT_S = 5.0  # a report is ingested well inside this, or never
TRIALS = 3  # a trial this process was too slow for is made again, twice
_FIELD = 24  # bits of each count in ``never_rows``
_MASK = (1 << _FIELD) - 1
# what the server process keeps for the probe and the window's checks: the
# service (``load_rules``), the mix's health script (``drive_before_window``)
_RUN = {}

# -- frames --------------------------------------------------------------------
MAX_ROWS_PER_FRAME = wire.MAX_ROWS_PER_FRAME
SINGLE_REPLIES = ((wire.FLOW,), wire.SINGLE_RSP)
BATCH_REPLIES = ((wire.BATCH_FLOW,), wire.RSP_ROW)
# a batch reply's row with ``remaining`` under the name ``probe.exchange``
# hands back (it returns ``status`` and ``wait_ms``)
_REMAINING_ROW = np.dtype({"names": ["status", "wait_ms"],
                           "formats": ["i1", ">i4"], "offsets": [0, 1],
                           "itemsize": 9})


def report_xid(xid: int) -> int:
    """The xid a frame's report goes under: outside every range a generator
    or the probe pairs replies by."""
    return -1 - int(xid)


def encode_report(xid: int, flow_ids, rt_ms, exc) -> bytes:
    """One OUTCOME_REPORT frame of the rows whose ``rt_ms`` is not negative;
    no bytes where there is none."""
    rt_ms = np.asarray(rt_ms)
    done = np.flatnonzero(rt_ms >= 0)
    if not done.size:
        return b""
    rep = np.empty(done.size, OUTCOME_ROW)
    rep["flow_id"] = np.asarray(flow_ids)[done]
    rep["rt_ms"] = rt_ms[done]
    rep["exc"] = np.asarray(exc)[done]
    return wire._BATCH_HEAD.pack(
        5 + 2 + done.size * OUTCOME_ROW.itemsize, report_xid(xid),
        OUTCOME_REPORT, done.size) + rep.tobytes()


def encode_batch(xid: int, flow_ids, counts, rt_ms, exc, told_ids, told_rt,
                 told_exc) -> bytes:
    """The report of the connection's previous frame, then the BATCH_FLOW
    request frame of this one's rows."""
    return encode_report(xid, told_ids, told_rt, told_exc) + wire.encode_batch(
        xid, flow_ids, counts)


def encode_singles(first_xid: int, flow_ids, counts, rt_ms, exc, told_ids,
                   told_rt, told_exc) -> np.ndarray:
    """One-row FLOW frames. They are fixed-size and carry no report: a mix
    of one-row frames has no ``health``."""
    if (np.asarray(told_rt) >= 0).any():
        raise ValueError("a one-row frame cannot carry a completion report")
    return wire.encode_singles(first_xid, flow_ids, counts)


class AdmittedReports:
    """The session of a mix whose ``reports`` are ``admitted``: a call
    completes only if it was let through. ``back`` keeps, per connection,
    the completions (the frame's own ``rt_ms`` and ``exc`` columns) of the
    rows answered OK or SHOULD_WAIT; ``encode`` puts all that the connection
    has kept in front of its next request frame, as reports of at most
    ``MAX_ROWS_PER_FRAME`` rows, and forgets it. The frame's ``told_*``
    columns (the previous frame's rows, verdicts unseen) are not read. A
    frame that is lost completes nothing: there is nothing to forget."""

    def __init__(self, n_connections: int):
        self.locks = [threading.Lock() for _ in range(n_connections)]
        self.done = [[] for _ in range(n_connections)]  # (ids, rt, exc) each

    def encode(self, ci: int, xid: int, flow_ids, counts, *_rest) -> bytes:
        with self.locks[ci]:
            kept, self.done[ci] = self.done[ci], []
        out = b""
        if kept:
            ids, rt, exc = (np.concatenate(col) for col in zip(*kept))
            for at in range(0, len(ids), MAX_ROWS_PER_FRAME):
                to = at + MAX_ROWS_PER_FRAME
                out += encode_report(xid, ids[at:to], rt[at:to], exc[at:to])
        return out + wire.encode_batch(xid, flow_ids, counts)

    def back(self, ci: int, xid: int, cols, reply_rows, t: float) -> None:
        m = min(len(reply_rows), len(cols[0]))
        st = reply_rows["status"][:m]
        ids, rt, exc = cols[0][:m], cols[2][:m], cols[3][:m]
        let = (rt >= 0) & ((st == OK) | (st == SHOULD_WAIT))
        if let.any():
            with self.locks[ci]:
                self.done[ci].append((ids[let], rt[let], exc[let]))


def Session(tr: dict, dep, seed: int, proc: int, n_connections: int):
    """``AdmittedReports`` where the traffic file states ``"reports":
    "admitted"``; None (every guarded row reports, frames encoded before the
    window) where it does not state the key."""
    reports = tr.get("reports")
    if reports not in (None, "admitted"):
        raise ValueError(f"reports: {reports!r}; 'admitted' or no key")
    return None if reports is None else AdmittedReports(n_connections)


class Deployment(flow.Deployment):
    def __init__(self, spec: dict):
        r = spec["rules"]
        self.guarded_ranks = int(r["guarded_ranks"])
        self.degrade = dict(r["degrade"])
        self.recovery_ms = int(self.degrade["recovery_timeout_ms"])
        super().__init__(spec)
        if self.n_plain < self.namespaces * (self.guarded_ranks + 1):
            raise ValueError("too few plain flows for the guarded ranks")
        self.n_metered_keys = len(self.metered_counts) * self.namespaces
        self.n_guarded_keys = self.guarded_ranks * self.namespaces

    # -- breakers ------------------------------------------------------------
    def is_guarded(self, flow_ids) -> np.ndarray:
        f = np.asarray(flow_ids, np.int64)
        probe_ns = np.isin(f % self.namespaces, self.probe_namespaces)
        return ((f < PROBE_BASE) & (f // self.namespaces < self.guarded_ranks)
                & ~probe_ns)

    def guarded_index(self, flow_ids) -> np.ndarray:
        """Dense index of a guarded plain flow: ``ns * guarded_ranks + rank``."""
        f = np.asarray(flow_ids, np.int64)
        return (f % self.namespaces) * self.guarded_ranks + f // self.namespaces

    def strategy_of(self, flow_ids) -> np.ndarray:
        """The strategy of plain guarded flows, by rank."""
        return (np.asarray(flow_ids, np.int64) // self.namespaces) % 3

    def _breaker(self, strategy: int) -> dict:
        """Keyword arguments of a ``DegradeRule`` / ``Breaker``."""
        d = self.degrade
        kw = {"strategy": strategy,
              "min_request_amount": int(d["min_request_amount"]),
              "stat_interval_ms": int(d["stat_interval_ms"]),
              "recovery_timeout_ms": self.recovery_ms}
        if strategy == breaker_reference.SLOW_REQUEST_RATIO:
            kw.update(threshold=float(d["slow_ratio_threshold"]),
                      slow_rt_ms=int(d["slow_rt_ms"]))
        elif strategy == breaker_reference.ERROR_RATIO:
            kw["threshold"] = float(d["error_ratio_threshold"])
        else:
            kw["threshold"] = float(d["error_count_threshold"])
        return kw

    def degrade_rules(self):
        """Every breaker as ``(flow_id, namespace_name, keyword arguments)``."""
        for ns in self.traffic_namespaces():
            for rank in range(self.guarded_ranks):
                yield (int(self.flow_id(ns, rank)), f"ns{ns}",
                       self._breaker(rank % 3))
        ns = f"ns{self.probe_namespaces[0]}"
        for fid, _count, _b, role in self.probe_rules:
            if role in _PROBE_STRATEGY:
                yield fid, ns, self._breaker(_PROBE_STRATEGY[role])

    # -- the ledger's view of a row --------------------------------------------
    def ledger_counts(self) -> np.ndarray:
        """The metered ranks' counts, then no limit on the guarded slots'
        two row counts (they are held by ``window_checks``)."""
        return np.concatenate([self.metered_count_of_index(),
                               np.full(2 * self.n_guarded_keys, 1e12)])

    def ledger_view(self, cols, st, remaining):
        """As the flow family's, and every row of a guarded flow under one
        of its slot's two keys: through the breaker, or DEGRADED."""
        ids, acq = cols[0], cols[1]
        metered = self.is_metered(ids)
        guarded = self.is_guarded(ids)
        shed = st == DEGRADED
        brown = (st == OK) & (remaining == 0) & ~metered
        ok_m = metered & (st == OK)
        through = guarded & ((st == OK) | (st == BLOCKED))
        refused = guarded & shed
        never = (int(((st == BLOCKED) & ~metered).sum())
                 + (int((shed & ~guarded).sum()) << _FIELD))
        m, g = self.n_metered_keys, self.n_guarded_keys
        keys = np.concatenate([
            self.metered_index(ids[ok_m]),
            m + self.guarded_index(ids[through]),
            m + g + self.guarded_index(ids[refused])])
        tokens = np.concatenate([
            acq[ok_m], np.ones(int(through.sum()) + int(refused.sum()),
                               acq.dtype)])
        return DECIDED[st], brown, never, keys, tokens

    def open_span_breaches(self, through, shed, lat_max) -> int:
        """Guarded slots with rows through in a bin wholly inside an OPEN
        span (see the module's head). ``through`` and ``shed`` are ``[slots,
        bins]`` row counts, ``lat_max`` the slowest reply of each bin."""
        n_bins = through.shape[1]
        span = int(round(self.recovery_ms / 1000.0 / BIN_S))
        none_before = np.ones_like(shed, bool)
        some_before = np.zeros_like(shed, bool)
        for k in (1, 2, 3):
            none_before[:, k:] &= shed[:, :-k] == 0
            none_before[:, :k] = False
            some_before[:, k:] |= through[:, :-k] > 0
        firsts = np.argwhere((shed > 0) & none_before & some_before)
        slow = np.asarray(lat_max, np.float64)
        breached = set()
        for slot, b in firsts:
            late = int(slow[b:min(n_bins, b + span + 1)].max() / BIN_S)
            lo, hi = b + 2 + late, b + span - 2 - late
            if hi > lo and through[slot, lo:min(hi, n_bins)].sum() > 0:
                breached.add(int(slot))
        return len(breached)

    def parity_shares(self, through, shed) -> dict:
        """The DEGRADED share of the window's rows on guarded flows of even
        rank, and of odd."""
        odd = (np.arange(through.shape[0]) % self.guarded_ranks) % 2 == 1
        out = {}
        for name, mine in (("even", ~odd), ("odd", odd)):
            n_shed = float(shed[mine].sum())
            out[name] = n_shed / max(n_shed + float(through[mine].sum()), 1.0)
        return out

    def window_checks(self, client: dict) -> list:
        n = client["never_rows"]
        checks = [("unmetered rows BLOCKED", n & _MASK, 0),
                  ("unguarded rows DEGRADED", n >> _FIELD & _MASK, 0)]
        adm = client.get("admitted")
        if adm is None or not np.size(adm):
            return checks
        m, g = self.n_metered_keys, self.n_guarded_keys
        through, shed = adm[m:m + g], adm[m + g:m + 2 * g]
        checks.append(("guarded flows with rows through inside an OPEN span",
                       self.open_span_breaches(through, shed,
                                               client["lat_max"]), 0))
        health = _RUN.get("health") or {}
        expect = health.get("expect")
        if expect is not None:
            got = self.parity_shares(through, shed)
            for parity, want in expect["degraded_share"].items():
                checks.append((
                    f"DEGRADED share of {parity} guarded ranks off the "
                    f"script's {want}, in thousandths",
                    round(1000 * abs(got[parity] - float(want))),
                    round(1000 * float(expect["tolerance"]))))
            hot = expect.get("hottest_flows")
            if hot is not None and (through.shape[1] * BIN_S
                                    >= 2 * float(health["period_s"])):
                got = self.hottest_flow_shares(through, shed)
                for rank, share in enumerate(got.tolist()):
                    parity = "odd" if rank % 2 else "even"
                    lo, hi = hot[parity]
                    mid, half = (lo + hi) / 2.0, (hi - lo) / 2.0
                    checks.append((
                        f"DEGRADED share of the hottest tenant's guarded "
                        f"flow of rank {rank} off {mid:g}, the middle of "
                        f"the {parity} band, in thousandths",
                        round(1000 * abs(share - mid)), round(1000 * half)))
        return checks

    def hottest_flow_shares(self, through, shed) -> np.ndarray:
        """The DEGRADED share of the window's rows on each guarded flow of
        the hottest tenant, by rank (the mix's tenants are the traffic
        namespaces in order of popularity; that tenant sends many frames a
        second, so its breakers follow the script cycle by cycle). A flow
        without a row reads 2, outside any band."""
        ns = int(self.traffic_namespaces()[0])
        at = ns * self.guarded_ranks + np.arange(self.guarded_ranks)
        n_shed = shed[at].sum(axis=1).astype(np.float64)
        rows = n_shed + through[at].sum(axis=1)
        return np.where(rows > 0, n_shed / np.maximum(rows, 1.0), 2.0)

    # -- rules -----------------------------------------------------------------
    def _probe_rules(self) -> list:
        """``(flow_id, count, behaviour, role)`` of the probe's own flows:
        the flow family's, then the breaker checks', each check with flows
        of its own."""
        out = []
        fid = PROBE_BASE
        p = self.probe
        for _set in range(int(p["sets"])):
            for c in p["tight_counts"]:
                out.append((fid, float(c), DEFAULT, "tight"))
                fid += 1
            out.append((fid, float(p["big_count"]), DEFAULT, "big"))
            fid += 1
            out.append((fid, float(p["paced_count"]), RATE_LIMITER, "paced"))
            fid += 1
            for role in _PROBE_STRATEGY:
                out.append((fid, self.unmetered_count, DEFAULT, role))
                fid += 1
        return out

    def probe_set(self, k: int) -> dict:
        per = len(self.probe_rules) // int(self.probe["sets"])
        out = {"tight": []}
        for fid, count, _b, role in self.probe_rules[k * per:(k + 1) * per]:
            if role == "tight":
                out["tight"].append((fid, count))
            else:
                out[role] = (fid, count)
        return out


Deployment.family = sys.modules[__name__]

# the probe's breaker flows by role, and the strategy of each
_S, _R, _C = (breaker_reference.SLOW_REQUEST_RATIO,
              breaker_reference.ERROR_RATIO, breaker_reference.ERROR_COUNT)
_PROBE_STRATEGY = {
    "ratio_at": _R, "ratio_past": _R, "ratio_under_min": _R,
    "ratio_at_min": _R, "ratio_many": _R,
    "slow_at": _S, "slow_past": _S, "slow_under_min": _S, "slow_at_min": _S,
    "count_at": _C, "count_past": _C,
    "holds": _C, "one": _R, "recover": _S, "rollback": _R, "fence": _S}


# -- the health script -----------------------------------------------------------
def _overlap(start: float, length: float, n_slots: int,
             slot_s: float) -> np.ndarray:
    """The share of each slot of a cycle of ``n_slots`` that ``[start, start
    + length)`` (cyclic, seconds) covers."""
    period = n_slots * slot_s
    edges = np.arange(n_slots + 1) * slot_s
    out = np.zeros(n_slots)
    for lo in (start % period, start % period - period):
        hi = lo + min(length, period)
        out += np.clip(np.minimum(edges[1:], hi) - np.maximum(edges[:-1], lo),
                       0.0, None)
    return out / slot_s


def stagger(weights, sick_s, shed_from_s, shed_s, period_s: float,
            slot_s: float, first=()) -> np.ndarray:
    """Phases (seconds, on the ``slot_s`` grid) of ``len(weights)`` flows,
    one after another: those ``first`` names, in that order, then the others
    by weight (a stable sort), each at the start that leaves the fullest
    slot of the two loads lowest: the weight sick in a slot, and the weight
    expected DEGRADED in it (from ``shed_from_s`` after the phase, for
    ``shed_s``), each against its own mean."""
    w = np.asarray(weights, np.float64)
    n_slots = int(round(period_s / slot_s))
    sick_s, shed_from_s, shed_s = (np.asarray(a, np.float64)
                                   for a in (sick_s, shed_from_s, shed_s))
    mean = np.array([(w * np.minimum(sick_s, period_s)).sum(),
                     (w * np.minimum(shed_s, period_s)).sum()]) / period_s
    load = np.zeros((2, n_slots))
    placed = set(first)
    order = list(first) + [i for i in np.argsort(-w, kind="stable")
                           if i not in placed]
    phase = np.zeros(len(w))
    for i in order:
        best = None
        for s in range(n_slots):
            add = np.stack([
                _overlap(s * slot_s, sick_s[i], n_slots, slot_s),
                _overlap(s * slot_s + shed_from_s[i], shed_s[i], n_slots,
                         slot_s)]) * w[i]
            worst = float(((load + add) / mean[:, None]).max())
            if best is None or worst < best[0] - 1e-12:
                best = (worst, s, add)
        load += best[2]
        phase[i] = best[1] * slot_s
    return phase


class HealthScript:
    """When the dependency behind each guarded flow is sick: the file's, the
    same for every seed (see the module's head)."""

    def __init__(self, health: dict, dep, tenants, tenant_p, rank_p):
        self.h, self.dep = health, dep
        self.period = float(health["period_s"])
        g = dep.guarded_ranks
        ranks = np.arange(g)
        sick_by_rank = np.where(ranks % 2 == 1, float(health["sick_s"]["odd"]),
                                float(health["sick_s"]["even"]))
        delay = health["trip_delay_s"]
        delay_by_rank = np.asarray([delay["slow"], delay["ratio"],
                                    delay["count"]], np.float64)[ranks % 3]
        rec = dep.recovery_ms / 1000.0
        # DEGRADED from the trip until a probe finds the dependency healthy:
        # one recovery timeout after a 1 s sickness, two after a 3 s one
        cycles = np.ceil((sick_by_rank - delay_by_rank) / rec)
        n = len(tenants)
        weights = (np.asarray(tenant_p)[:, None]
                   * np.asarray(rank_p)[None, :g]).reshape(-1)
        hottest = int(np.argmax(tenant_p))
        first = [hottest * g + int(r) for r in np.argsort(
            -weights[hottest * g:(hottest + 1) * g], kind="stable")]
        phase = stagger(weights, np.tile(sick_by_rank, n),
                        np.tile(delay_by_rank, n), np.tile(cycles * rec, n),
                        self.period, float(health["slot_s"]), first)
        # by guarded slot (``Deployment.guarded_index``)
        self.sick_s = np.tile(sick_by_rank, dep.namespaces)
        self.phase = np.zeros(dep.n_guarded_keys)
        self.weights = np.zeros(dep.n_guarded_keys)
        for i, ns in enumerate(tenants):
            self.phase[ns * g:(ns + 1) * g] = phase[i * g:(i + 1) * g]
            self.weights[ns * g:(ns + 1) * g] = weights[i * g:(i + 1) * g]

    def sick(self, slots, t_s) -> np.ndarray:
        """Whether the dependencies of the guarded ``slots`` are sick at
        schedule time ``t_s`` (arrays that broadcast together)."""
        since = (np.asarray(t_s, np.float64) - self.phase[slots]) % self.period
        return since < self.sick_s[slots]

    def sick_weight(self, t_s: float) -> float:
        """The traffic-weighted share of guarded rows whose dependency is
        sick at ``t_s``."""
        every = np.arange(len(self.phase))
        return float((self.weights * self.sick(every, t_s)).sum()
                     / self.weights.sum())


# -- the generator's side: drawing rows -----------------------------------------
class Mix(flow.Mix):
    """The flow family's rows (a seed's flows and acquires are those the flow
    family draws for it), with the completions of the rows on guarded flows
    drawn from a stream of their own and, beside each frame, the completions
    its report carries. ``rows`` is what a generator calls for its window:
    frame ``j`` of process ``p`` is frame ``p + j * processes`` of the open
    loop's schedule, and a dependency's health follows that frame's due
    time. ``frames(n)`` is a schedule from its own frame 0 too when ``n`` is
    the warm-up's length (``loadgen._build``), and healthy otherwise."""

    def __init__(self, tr: dict, deployment, seed: int, salt: int):
        super().__init__(tr, deployment, seed, salt)
        self.out_rng = np.random.default_rng([int(seed), int(salt), 7793])
        self.health = tr.get("health")
        n_procs = int(tr.get("processes", 1))
        self.proc = salt - 1 if 1 <= salt <= n_procs else 0
        self.n_procs = n_procs
        self.conns = int(tr.get("connections", 1))
        self.frame_s = self.warm_frames = None
        if self.health is None:
            return
        if tr["loop"] != "open" or tr.get("phases") or tr["msg"] != "batch":
            raise ValueError("the health script is written for a constant-"
                             "rate open loop of batch frames")
        self.frame_s = self.frame_rows / float(tr["rate_rows_per_s"])
        self.start_s = float(self.health.get("start_s", 0.0))
        n_warm = len(traffic.open_schedule(tr, float(tr.get(
            "warm_seconds", 1.5))))
        self.warm_frames = len(range(self.proc, n_warm, n_procs))

    @functools.cached_property
    def script(self):
        """The health script, staggered when first asked for (a mix that
        only apportions tenants never does); None without ``health``."""
        if self.health is None:
            return None
        return HealthScript(self.health, self.d, self.tenants, self.tenant_p,
                            np.diff(self.flow_cdf, prepend=0.0))

    def completions(self, ids: np.ndarray, due_s):
        """``(rt_ms, exc)`` of the rows ``ids`` (``[frames, rows]``), frame
        ``k`` due ``due_s[k]`` seconds into its schedule (None: healthy)."""
        rt = np.full(ids.shape, -1, np.int32)
        exc = np.zeros(ids.shape, np.uint8)
        if self.health is None:
            return rt, exc
        guarded = self.d.is_guarded(ids)
        lo, hi = self.health["healthy"]["rt_ms"]
        u = self.out_rng.random((3,) + ids.shape)
        rt_ok = (lo + np.floor(u[0] * (hi - lo + 1))).astype(np.int32)
        if due_s is None:
            return np.where(guarded, rt_ok, rt).astype(np.int32), exc
        s = self.health["sick"]
        slots = np.where(guarded, self.d.guarded_index(ids), 0)
        ill = guarded & self.script.sick(slots, np.asarray(due_s)[:, None])
        slow = self.d.strategy_of(ids) == breaker_reference.SLOW_REQUEST_RATIO
        slo, shi = s["slow_rt_ms"]
        rt_bad = (slo + np.floor(u[2] * (shi - slo + 1))).astype(np.int32)
        bad_slow = ill & slow & (u[1] < float(s["slow_share"]))
        bad_exc = ill & ~slow & (u[1] < float(s["exc_share"]))
        return (np.where(guarded, np.where(bad_slow, rt_bad, rt_ok),
                         rt).astype(np.int32), bad_exc.astype(np.uint8))

    def _with(self, ids, acq, due_s):
        rt, exc = self.completions(ids, due_s)
        told = []
        for col, none in ((ids, 0), (rt, -1), (exc, 0)):
            prev = np.full_like(col, none)
            prev[self.conns:] = col[:-self.conns or None]
            told.append(prev)
        return (ids, acq, rt, exc) + tuple(told)

    def _due(self, n: int):
        if self.health is None:
            return None
        return self.start_s + (self.proc + self.n_procs
                               * np.arange(n)) * self.frame_s

    def rows(self, frame_tenants: np.ndarray):
        ids, acq = super().rows(frame_tenants)
        return self._with(ids, acq, self._due(len(frame_tenants)))

    def frames(self, n_frames: int):
        ids, acq = super().rows(self.frame_tenants(n_frames))
        scripted = self.health is not None and n_frames == self.warm_frames
        return self._with(ids, acq, self._due(n_frames) if scripted else None)


# -- the program's side ---------------------------------------------------------
def service_args(dep) -> dict:
    """Nothing beyond the engine's sizes. A program from before this family
    warms no outcome step and does not say what its breaker arm did: said
    here, before anything is built, so that such a tree fails at once and
    cleanly."""
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.metrics.server import ServerMetrics

    if not (hasattr(ServerMetrics, "count_outcome_report")
            and hasattr(DefaultTokenService, "_ensure_outcome_warm")):
        raise SystemExit(
            "this program does not warm its outcome step or count its "
            "breaker arm (DefaultTokenService._ensure_outcome_warm, "
            "ServerMetrics.count_outcome_report): the breaker family cannot "
            "run its cell on it")
    return {}


def load_rules(service, dep) -> int:
    """The flow rules, then the breakers through the service's public entry
    for them; both counts are checked."""
    from sentinel_tpu.engine.rules import DegradeRule

    n_rules = flow.load_rules(service, dep)
    wanted = [DegradeRule(flow_id=fid, namespace=ns, **kw)
              for fid, ns, kw in dep.degrade_rules()]
    service.load_degrade_rules(wanted)
    n_breakers = len(service.current_degrade_rules())
    n_rules_after = len(service.current_rules())
    if n_breakers != len(wanted) or n_rules_after != n_rules:
        raise RuntimeError(
            f"{n_breakers} breakers loaded of {len(wanted)} in the file, "
            f"{n_rules_after} flow rules where {n_rules} were")
    _RUN["service"] = service
    return n_rules


def drive_before_window(built, tr: dict, dep, seed: int, compiles: list,
                        say) -> list:
    """As the flow family's: each reachable fused depth as a backlog of the
    mix's own rows, in process; then one report of every size the outcome
    step pads to, so that nothing is left for the warm-up's traffic to
    compile and the number of its passes does not hang on it."""
    _RUN["health"] = tr.get("health")
    depths = flow.reachable_depths(dep, tr, built.server)
    mix = Mix(tr, dep, seed, 991)
    cap = int(dep.spec["engine"]["batch_size"])
    for d in depths:
        cols = mix.frames(-(-d * cap // mix.frame_rows))
        n0 = len(compiles)
        built.service.request_batch_arrays(
            *[c.reshape(-1)[:d * cap] for c in cols[:2]])
        say(f"warm-up: depth-{d} backlog of {d * cap} rows in process, "
            f"{len(compiles) - n0} compiles")
    ids, _acq, rt, exc = (c.reshape(-1) for c in mix.frames(2)[:4])
    done = np.flatnonzero(rt >= 0)
    n0 = len(compiles)
    n = 1
    while n <= done.size:
        at = done[:n]
        built.service.report_outcomes(ids[at], rt[at], exc[at])
        n *= 4
    say(f"warm-up: healthy reports of 1 to {n // 4} rows in process, "
        f"{len(compiles) - n0} compiles")
    return depths


progress = flow.progress


# -- the probe's sets -----------------------------------------------------------
class _Checks(flow._Checks):
    """The flow family's checks on this family's frames, and the eight of
    the breakers."""

    def __init__(self, p):
        super().__init__(p)
        self.rec_ms = p.dep.recovery_ms
        self.control = dict(_RUN.get("reference_control", {}))
        self.ref = breaker_reference.for_deployment(p.dep, **self.control)
        self.t0 = time.monotonic()

    # -- the flow family's checks, on this family's frames -----------------------
    def _send(self, ids, acq):
        n = len(ids)
        none, zero = np.full(n, -1, np.int32), np.zeros(n, np.uint8)
        return self.p.send(np.asarray(ids, np.int64), np.asarray(acq, np.int32),
                           none, zero, np.zeros(n, np.int64), none, zero)

    # -- telling and asking ---------------------------------------------------------
    def _now(self) -> int:
        """The client's clock in ms."""
        return 10_000 + int((time.monotonic() - self.t0) * 1000)

    def _ref(self, *fids):
        return breaker_reference.for_deployment(self.dep, only=set(fids),
                                                **self.control)

    @staticmethod
    def _ingested():
        """Completion rows the server's outcome steps have taken so far, if
        this process holds the server: the program's own counter, bumped as
        a report's step is issued (``service.outcome_stats()`` says the same
        and reads the whole outcome window from the device to say it, a
        tenth of a second at 100k flows, which the retry-afters compared
        here would carry)."""
        if _RUN.get("service") is None:
            return None
        from sentinel_tpu.metrics.server import server_metrics

        return int(server_metrics().arm_totals()["outcome_step_rows_total"])

    def _tell(self, ref, ids, rt, exc) -> float:
        """One report through the door, and to ``ref`` once the server has
        counted its rows (a door with no server behind it in this process:
        after a moment). Returns the seconds from the send to that moment:
        the server stamped the report somewhere inside them, ``ref`` at
        their end."""
        before = self._ingested()
        raw = encode_report(self.p.xid, ids, np.asarray(rt, np.int32),
                            np.asarray(exc, np.uint8))
        # a one-row request on an unmetered flow behind it, so that the
        # exchange ends on a reply
        bg = self.dep.flow_id(self.dep.probe_namespaces[0],
                              self.dep.flows_per_namespace() - 1)
        raw += wire.encode_batch(self.p.xid, [bg], [1])
        self.p.xid += 16
        t_sent = time.monotonic()
        probe.exchange(self.p.port, [raw], 1, False,
                       (SINGLE_REPLIES, BATCH_REPLIES))
        if before is None:
            time.sleep(0.05)
        else:
            deadline = time.monotonic() + INGEST_WAIT_S
            while self._ingested() < before + len(ids):
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"a report of {len(ids)} rows was not ingested "
                        f"within {INGEST_WAIT_S} s")
                time.sleep(0.002)
        ref.report(self._now(), ids, rt, exc)
        return time.monotonic() - t_sent

    def _ask(self, ref, fids, exact: bool = True):
        """One frame of one-token rows on ``fids`` to the server and to
        ``ref``: ``(status, mismatches, seconds)``. A row differs by its
        status, or by the retry-after of a DEGRADED row: to the millisecond
        where it is a whole timeout, within ``RETRY_SLACK_MS`` where it is
        what is left of one by the client's clock (``exact=False``)."""
        ids = np.asarray(fids, np.int64)
        raw = wire.encode_batch(self.p.xid, ids, np.ones(len(ids), np.int32))
        self.p.xid += len(ids) + 16
        t = self._now()
        status, left, took = probe.exchange(
            self.p.port, [raw], len(ids), False,
            (SINGLE_REPLIES, ((wire.BATCH_FLOW,), _REMAINING_ROW)))
        want, want_left = ref.decide_frame(t, ids, np.ones(len(ids), np.int32))
        want = np.asarray(want, np.int8)
        shed = (status == DEGRADED) & (want == DEGRADED)
        off = np.abs(left.astype(np.int64) - np.asarray(want_left, np.int64))
        bad = (status != want) | (shed & (off > (0 if exact
                                                 else RETRY_SLACK_MS)))
        return status, int(bad.sum()), took

    def _heal(self, *fids) -> None:
        """Leave ``fids`` CLOSED with no history the checks could meet: on a
        server that has just started they are; one probed before (many seeds
        against one server) may hold them OPEN."""
        ref = self._ref(*fids)  # follows nothing: its verdicts are not read
        twice = [f for f in fids for _ in (0, 1)]  # a probe, and one behind it
        for _try in range(4):
            status, _bad, _t = self._ask(ref, twice)
            shed = status == DEGRADED
            if not shed.any():
                if _try:
                    time.sleep(int(self.dep.degrade["stat_interval_ms"])
                               / 1000.0 + 0.1)
                return
            time.sleep(self.rec_ms / 1000.0 + 0.05)
            self._ask(ref, list(fids))  # the probes
            self._tell(ref, list(fids), [1] * len(fids), [0] * len(fids))
        raise RuntimeError(f"flows {fids} stayed DEGRADED")

    def _trip(self, name: str, cases) -> None:
        """``cases``: ``(role, rt_ms list, exc list)``, one report for all of
        them, then two rows on each flow in one frame. A trial whose answer
        came more than ``flow.MAX_CHECK_S`` after its report was sent has
        left the stat interval it asks about (the server stamped the report
        when it came, the reference when this process saw it counted): it
        is void and made again, the third as it comes."""
        fids = [self.flows[role][0] for role, _rt, _e in cases]
        ids, rt, exc = [], [], []
        for fid, (_role, r, e) in zip(fids, cases):
            ids, rt, exc = ids + [fid] * len(r), rt + list(r), exc + list(e)
        for trial in range(TRIALS):
            self._heal(*fids)
            ref = self._ref(*fids)
            t_start = time.monotonic()
            self._tell(ref, ids, rt, exc)
            status, bad, took = self._ask(ref, [f for f in fids
                                                for _ in (0, 1)])
            spent = time.monotonic() - t_start
            if spent <= flow.MAX_CHECK_S:
                break
            self._settle()
        said = ", ".join(
            f"{role} " + ("DEGRADED" if status[2 * k] == DEGRADED else "OK")
            for k, (role, _r, _e) in enumerate(cases))
        self._record(name, len(ids) + 2 * len(fids), bad, spent,
                     f"; {said} (asked in {took * 1e3:.1f} ms"
                     + (f", trial {trial + 1}" if trial else "") + ")")

    def _settle(self) -> None:
        """After a void trial: what it reported leaves the stat interval."""
        time.sleep(int(self.dep.degrade["stat_interval_ms"]) / 1000.0 + 0.1)

    # -- the checks ------------------------------------------------------------------
    def trip_ratio(self) -> None:
        self._trip("trip_ratio", [
            ("ratio_at", [5] * 10, [1] * 5 + [0] * 5),
            ("ratio_past", [5] * 10, [1] * 6 + [0] * 4),
            ("ratio_under_min", [5] * 4, [1] * 4),
            ("ratio_at_min", [5] * 5, [1] * 3 + [0] * 2),
            ("ratio_many", [5] * 513, [1] * 257 + [0] * 256)])

    def trip_slow(self) -> None:
        cut = int(self.dep.degrade["slow_rt_ms"])
        self._trip("trip_slow", [
            ("slow_at", [cut + 1] * 6 + [cut] * 4, [0] * 10),
            ("slow_past", [cut + 1] * 7 + [cut] * 3, [0] * 10),
            ("slow_under_min", [cut + 200] * 4, [0] * 4),
            ("slow_at_min", [cut + 200] * 5, [0] * 5)])

    def trip_count(self) -> None:
        c = int(self.dep.degrade["error_count_threshold"])
        self._trip("trip_count", [
            ("count_at", [5] * 10, [1] * c + [0] * (10 - c)),
            ("count_past", [5] * (c + 1), [1] * (c + 1))])

    def lifecycle(self) -> None:
        """``open_holds``, ``probe_one``, ``recover``, ``rollback`` and
        ``fence`` on one time line: five flows tripped by one report, asked
        while OPEN, then past the timeout, each probe and its report. The
        time line is made again (the third as it comes) where this process
        was too slow for what it compares: the answer that opens the flows
        more than ``flow.MAX_CHECK_S`` after their report was sent, or an
        exchange whose length the retry-afters hang on (the opening answer,
        the probes' report, the last frame) longer than their slack."""
        for trial in range(TRIALS):
            records, void = self._lifecycle_once()
            if not void:
                break
            self._settle()
        for name, rows, bad, took, note in records:
            self._record(name, rows, bad, took,
                         note + (f" (trial {trial + 1})" if trial else ""))

    def _lifecycle_once(self):
        """``(records, void)`` of one time line."""
        names = ("holds", "one", "recover", "rollback", "fence")
        fids = [self.flows[n][0] for n in names]
        holds, one, rec, roll, fence = fids
        slack_s = RETRY_SLACK_MS / 1000.0
        records = []
        self._heal(*fids)
        ref = self._ref(*fids)
        cut = int(self.dep.degrade["slow_rt_ms"])
        slow = {rec, fence}  # the SLOW_REQUEST_RATIO flows of the five
        ids = [f for f in fids for _ in range(8)]
        t_start = time.monotonic()
        self._tell(ref, ids, [cut + 100 if f in slow else 5 for f in ids],
                   [0 if f in slow else 1 for f in ids])
        t_open = time.monotonic()
        _st, bad, took = self._ask(ref, fids)  # the step that opens them
        void = (time.monotonic() - t_start > flow.MAX_CHECK_S
                or took > slack_s)
        rows = len(ids) + len(fids)
        for k in (1, 2, 3):  # every row, until the timeout
            time.sleep(max(0.0, t_open + k * self.rec_ms / 4000.0
                           - time.monotonic()))
            _st, b, t = self._ask(ref, [holds] * 16, exact=False)
            bad, took, rows = bad + b, took + t, rows + 16
            void = void or t > slack_s
        records.append(("open_holds", rows, bad, took,
                        f"; 3 frames of 16 rows over "
                        f"{(time.monotonic() - t_open) * 1e3:.0f} ms"))
        # bad completions while OPEN, which the fence must hide later
        self._tell(ref, [fence] * 10, [cut + 100] * 10, [0] * 10)
        time.sleep(max(0.0, t_open + self.rec_ms / 1000.0 + 0.25
                       - time.monotonic()))
        status, bad, took = self._ask(ref, [one] * 64)
        records.append(("probe_one", 64, bad, took, f"; rows not DEGRADED: "
                        f"{np.flatnonzero(status != DEGRADED).tolist()}"))
        # the probes go first and alone, then their reports, then frames
        _st, bad_p, took = self._ask(ref, [rec, roll, fence])
        seen_in = self._tell(ref, [rec, roll, fence], [5, 5, 5], [0, 1, 0])
        st, bad_a, t = self._ask(ref, [rec] * 8 + [roll] * 8 + [fence] * 8,
                                 exact=False)
        void = void or seen_in > slack_s or t > slack_s
        for k, name in enumerate(("recover", "rollback", "fence")):
            mine = st[8 * k:8 * k + 8]
            records.append((name, 10, bad_p + bad_a, took + t,
                            f"; after the probe's report {mine.tolist()}"))
        return records, void


def probe_checks(p) -> list:
    """The checks of one probe, in the order they run."""
    c = _Checks(p)
    if p.single:
        p.say("probe: the breaker checks are skipped, one-row frames carry "
              "no report")
        return [c.tight, c.big, c.guard, c.paced]
    return [c.tight, c.big, c.guard, c.paced, c.trip_ratio, c.trip_slow,
            c.trip_count, c.lifecycle]


# -- the controls of control.py ----------------------------------------------------
def unguarded(service):
    """The service with the degrade rules taken out of its table: nothing is
    ever DEGRADED. (``server.build`` wraps the service once the rules are
    loaded and before the door starts.)"""
    service.load_degrade_rules([])
    return service


def reference_8bit(service):
    """The sound service, held against the plain reference with every
    running total rounded to 8 significant bits."""
    _RUN["reference_control"] = {"lower_precision": True}
    return service


CONTROLS = {"over_admit": flow.OverAdmit, "unguarded": unguarded,
            "reference_8bit": reference_8bit}
