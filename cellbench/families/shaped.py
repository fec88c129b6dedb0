"""The shaped family: a flow table whose hot rules shape traffic (WARM_UP,
RATE_LIMITER, WARM_UP_RATE_LIMITER beside DEFAULT) and whose rows carry the
priority flag, asked with the flow family's FLOW and BATCH_FLOW frames.
``families/__init__.py`` lists what a family owns; everything a flow table
shares (the layout of ids and namespaces, the tenants and flows of a mix, the
door, the fused depths) is ``flow.py``'s and is used from there.

Layout. As ``flow.py``: plain flow ``i`` belongs to namespace ``ns{i %
namespaces}`` with popularity rank ``i // namespaces``. The
``rules.metered_ranks`` hottest ranks of every namespace are metered: rank
``r`` has behaviour ``r % 4`` (DEFAULT, WARM_UP, RATE_LIMITER,
WARM_UP_RATE_LIMITER) and the count ``rules.counts[behaviour][r // 4]``.
Every shaped rule has the file's ``cold_factor``, ``warm_up_period_sec`` and
``max_queueing_time_ms`` (upstream's defaults). The probe's flows live in the
first probe namespace, from ``PROBE_BASE`` up.

A row is ``(flow_id, acquire, prioritized)``: three columns ``(ids, acq,
prio)``. Mix parameters: the flow family's, plus

    prioritized   {"share": s}: each row is prioritized with probability
                  ``s``, drawn from the seed

The ledger. Keys ``0 .. M-1`` (``M`` metered plain flows) sum the tokens a
flow *granted* in a window: OK tokens, and for a paced flow the tokens of its
SHOULD_WAIT rows too (a paced grant is a grant, due later). Their limit is
the count, and for a paced flow ``count * (window + max queueing time) + 8``
tokens a window: grants decided inside a window are due up to the queue bound
after it, ``cost`` ms apart, and the first may carry the largest acquire (8).
Keys ``M .. 2M-1`` sum the tokens a DEFAULT flow *booked* in the next bucket
(SHOULD_WAIT on a prioritized row); their limit is the count. The window's
other counts, each with the limit 0: unmetered rows BLOCKED; SHOULD_WAIT
where none can be (an unprioritized row of a DEFAULT flow, any row of a
WARM_UP or an unmetered flow); a wait over its bound (the queue bound of a
paced flow, one bucket for a borrow).

The verdict's ``wait_ms`` reaches the ledger inside ``remaining``: the
harness hands ``ledger_view`` the reply's ``status`` and ``remaining`` fields
only, so this family's reply layouts name the eight bytes of ``remaining``
and ``wait_ms`` together as ``remaining`` (big-endian: tokens left above,
wait below) and ``ledger_view`` takes them apart again.

The probe's checks, every verdict and every wait against
``shaped_reference.py``, limit 0 mismatches. ``tight``, ``big``, ``guard``
and ``paced`` are the flow family's, with this family's frames. The counts
of the new flows are chosen so that no threshold sits where float32 and
float64 round apart (``Reference.closest`` says how near one came):

    warm        a cold WARM_UP flow of count 100 sent 60 rows in one frame:
                the first 33 pass (cold rate 100/3 = 33.3: a third of a
                token from the edge either way; a count divisible by three
                would put the threshold on it)
    warm_slide  another such flow filled to its cold rate, then four frames
                of five rows 200 ms apart. The frame that finds the
                server's next second syncs the curve (1000 stored tokens
                less the 33 passed: rate 34.87, one row more passes), the
                others pass none. The server's clock is its own and the
                probe cannot see where its seconds begin, so the reference
                decides the sequence once for every place the boundary can
                fall, and the server must equal one of them in every row
    warm_paced  a cold WARM_UP_RATE_LIMITER flow of count 100, 30 rows: cost
                round(1000 / 33.3) = 30 ms: OK, 16 waits of 30 .. 480 ms,
                13 BLOCKED
    occupy      a DEFAULT flow of count 50: 50 rows fill it, and the 4
                prioritized and 4 unprioritized rows behind them in the same
                frame are BLOCKED (the coming bucket lets go of nothing).
                0.9 s later, when the bucket that holds the 50 is the one to
                go, 100 rows, prioritized or not by the seed: the first 50
                prioritized ones are SHOULD_WAIT until the next bucket
                starts, every other row BLOCKED
    occupy_mature  0.25 s after that the 50 passed tokens have left and the
                50 booked ones count: rows of either kind are BLOCKED
    The last two share a reference. A paced flow of count 1 in the same
    frames (cost 1000 ms: its second grant waits ``1000 - elapsed``) tells
    the server's own time between the frames; the waits of the borrows tell
    where in its bucket the second frame fell. The reference is run for
    every phase of the first frame in its bucket and must match at one.

Controls: ``over_admit`` as the flow family's, and ``unshaped``: the
WARM_UP and WARM_UP_RATE_LIMITER rules loaded with no warm-up (DEFAULT and
RATE_LIMITER), which ``warm`` has to catch.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from cellbench import wire
from cellbench.deploy import (BLOCKED, DECIDED, DEFAULT, OK, RATE_LIMITER,
                              SHOULD_WAIT, WARM_UP, WARM_UP_RATE_LIMITER)
from cellbench.families import flow, shaped_reference

PROBE_BASE = flow.PROBE_BASE
BEHAVIOURS = ("DEFAULT", "WARM_UP", "RATE_LIMITER", "WARM_UP_RATE_LIMITER")
PACED = (RATE_LIMITER, WARM_UP_RATE_LIMITER)
MAX_ACQUIRE = 8  # the most tokens a row of any mix asks for
_FIELD = 24  # bits of each of the three counts in ``never_rows``

# -- frames: the flow family's, with the priority byte -------------------------
MAX_ROWS_PER_FRAME = wire.MAX_ROWS_PER_FRAME
# ``remaining`` spans the reply's remaining and wait_ms (see the ledger above)
_RSP_ROW = np.dtype({"names": ["status", "remaining", "wait_ms"],
                     "formats": ["i1", ">i8", ">i4"], "offsets": [0, 1, 5],
                     "itemsize": 9})
_SINGLE_RSP = np.dtype({
    "names": ["len", "xid", "type", "status", "remaining", "wait_ms"],
    "formats": [">u2", ">i4", "i1", "i1", ">i8", ">i4"],
    "offsets": [0, 2, 6, 7, 8, 12], "itemsize": 16})
SINGLE_REPLIES = ((wire.FLOW,), _SINGLE_RSP)
BATCH_REPLIES = ((wire.BATCH_FLOW,), _RSP_ROW)


def encode_batch(xid: int, flow_ids, counts, prio) -> bytes:
    """One BATCH_FLOW request frame, ``prio`` in the rows' priority byte."""
    n = len(flow_ids)
    if n > MAX_ROWS_PER_FRAME:
        raise ValueError(f"{n} rows exceed the wire's {MAX_ROWS_PER_FRAME}")
    rows = np.empty(n, wire.REQ_ROW)
    rows["flow_id"] = flow_ids
    rows["count"] = counts
    rows["prio"] = prio
    return wire._BATCH_HEAD.pack(5 + 2 + n * 13, xid, wire.BATCH_FLOW,
                                 n) + rows.tobytes()


def encode_singles(first_xid: int, flow_ids, counts, prio) -> np.ndarray:
    arr = wire.encode_singles(first_xid, flow_ids, counts)
    arr["prio"] = prio
    return arr


class Deployment(flow.Deployment):
    def __init__(self, spec: dict):
        r = spec["rules"]
        ranks = int(r["metered_ranks"])
        self.metered_behaviours = [k % 4 for k in range(ranks)]
        self.shaping = {"warm_up_period_sec": int(r["warm_up_period_sec"]),
                        "cold_factor": int(r["cold_factor"]),
                        "max_queueing_time_ms": int(r["max_queueing_time_ms"])}
        self.occupy_timeout_ms = int(r["occupy_timeout_ms"])
        # the rest is a flow table's, the counts of its metered ranks said
        # the flow family's way
        counts = [r["counts"][BEHAVIOURS[k % 4]][k // 4] for k in range(ranks)]
        super().__init__(dict(spec, rules=dict(r, metered_counts=counts)))
        self.spec = spec

    def behaviour_of(self, flow_ids) -> np.ndarray:
        """The control behaviour of plain flows (DEFAULT where unmetered)."""
        rank = np.asarray(flow_ids, np.int64) // self.namespaces
        table = np.asarray(self.metered_behaviours)
        return np.where(self.is_metered(flow_ids),
                        table[np.minimum(rank, len(table) - 1)], DEFAULT)

    # -- the ledger's view of a row ------------------------------------------
    def ledger_counts(self) -> np.ndarray:
        granted = self.metered_count_of_index()
        paced = np.isin(np.tile(self.metered_behaviours, self.namespaces),
                        PACED)
        queue = self.shaping["max_queueing_time_ms"]
        # per second, as run.window_invariants scales a key's count
        granted = np.where(
            paced, granted * (1 + queue / self.window_ms)
            + MAX_ACQUIRE * 1000.0 / self.window_ms, granted)
        return np.concatenate([granted, self.metered_count_of_index()])

    def ledger_view(self, cols, st, remaining):
        """As the flow family's, with the three kinds of row that can never
        be counted side by side in ``_FIELD`` bits each."""
        ids, acq, prio = cols
        remaining = np.asarray(remaining, np.int64)
        wait = remaining & 0xFFFFFFFF
        metered = self.is_metered(ids)
        beh = self.behaviour_of(ids)
        paced = np.isin(beh, PACED)
        waits = st == SHOULD_WAIT
        borrow = waits & metered & (beh == DEFAULT) & (prio != 0)
        brown = (st == OK) & (remaining >> 32 == 0) & ~metered
        granted = metered & ((st == OK) | (waits & paced))
        bound = np.where(paced, self.shaping["max_queueing_time_ms"],
                         self.bucket_ms)
        never = (int(((st == BLOCKED) & ~metered).sum())
                 + (int((waits & ~paced & ~borrow).sum()) << _FIELD)
                 + (int((waits & (wait > bound)).sum()) << 2 * _FIELD))
        n_keys = len(self.metered_counts) * self.namespaces
        keys = np.concatenate([self.metered_index(ids[granted]),
                               n_keys + self.metered_index(ids[borrow])])
        return (DECIDED[st], brown, never, keys,
                np.concatenate([acq[granted], acq[borrow]]))

    def window_checks(self, client: dict) -> list:
        n, mask = client["never_rows"], (1 << _FIELD) - 1
        return [("unmetered rows BLOCKED", n & mask, 0),
                ("SHOULD_WAIT rows where none can be", n >> _FIELD & mask, 0),
                ("waits over their bound", n >> 2 * _FIELD, 0)]

    # -- rules ---------------------------------------------------------------
    def _probe_rules(self) -> list:
        """``(flow_id, count, behaviour, role)`` of the probe's own flows."""
        p = self.probe
        per_set = ([(c, DEFAULT, "tight") for c in p["tight_counts"]] + [
            (p["big_count"], DEFAULT, "big"),
            (p["paced_count"], RATE_LIMITER, "paced"),
            (p["warm_count"], WARM_UP, "warm"),
            (p["warm_count"], WARM_UP, "warm_slide"),
            (p["warm_paced_count"], WARM_UP_RATE_LIMITER, "warm_paced"),
            (p["occupy_count"], DEFAULT, "occupy"),
            (p["clock_count"], RATE_LIMITER, "clock")])
        return [(PROBE_BASE + s * len(per_set) + k, float(c), b, role)
                for s in range(int(p["sets"]))
                for k, (c, b, role) in enumerate(per_set)]

    def probe_set(self, k: int) -> dict:
        per = len(self.probe_rules) // int(self.probe["sets"])
        out = {"tight": []}
        for fid, count, _b, role in self.probe_rules[k * per:(k + 1) * per]:
            if role == "tight":
                out["tight"].append((fid, count))
            else:
                out[role] = (fid, count)
        return out

    def rules(self):
        """Every rule as ``(flow_id, count, namespace_name, behaviour)``."""
        nm = len(self.metered_counts)
        for i in range(self.n_plain):
            rank = i // self.namespaces
            ns = f"ns{i % self.namespaces}"
            if rank < nm:
                yield (i, self.metered_counts[rank], ns,
                       self.metered_behaviours[rank])
            else:
                yield i, self.unmetered_count, ns, DEFAULT
        ns = f"ns{self.probe_namespaces[0]}"
        for fid, count, behaviour, _role in self.probe_rules:
            yield fid, count, ns, behaviour

    def reference_rules(self, only=None) -> dict:
        """``{flow_id: shaped_reference.Rule}``, of ``only`` those ids."""
        return {fid: shaped_reference.Rule(count, ns, behaviour,
                                           **self.shaping)
                for fid, count, ns, behaviour in self.rules()
                if only is None or fid in only}


Deployment.family = sys.modules[__name__]


# -- the generator's side: drawing rows ---------------------------------------
class Mix(flow.Mix):
    """The flow family's rows, each prioritized with the mix's share. The
    flag is drawn from a stream of its own, so a seed's flows and acquires
    are those the flow family draws for it."""

    def __init__(self, tr: dict, deployment, seed: int, salt: int):
        super().__init__(tr, deployment, seed, salt)
        self.share = float(tr["prioritized"]["share"])
        self.prio_rng = np.random.default_rng([int(seed), int(salt), 4099])

    def rows(self, frame_tenants: np.ndarray):
        ids, acq = super().rows(frame_tenants)
        return ids, acq, (self.prio_rng.random(ids.shape)
                          < self.share).astype(np.uint8)


# -- the program's side -------------------------------------------------------
def service_args(dep) -> dict:
    """Nothing beyond the engine's sizes. A program from before PR 31 does
    not say which arms of its step ran: said here, before anything is built,
    so that such a tree fails at once and cleanly."""
    from sentinel_tpu.metrics.server import ServerMetrics

    if not hasattr(ServerMetrics, "count_decide_arms"):
        raise SystemExit(
            "this program does not count its decide step's live arms "
            "(ServerMetrics.count_decide_arms, PR 31): the shaped family "
            "cannot tell that its cell ran them")
    return {}


def load_rules(service, dep) -> int:
    from sentinel_tpu.engine import ClusterFlowRule
    from sentinel_tpu.engine.rules import ThresholdMode

    service.load_rules(
        [ClusterFlowRule(fid, count, ThresholdMode.GLOBAL, ns,
                         control_behavior=behaviour, **dep.shaping)
         for fid, count, ns, behaviour in dep.rules()],
        ns_max_qps=dep.ns_max_qps,
    )
    n_rules = len(service.current_rules())
    if n_rules != dep.n_flows:
        raise RuntimeError(f"{n_rules} rules loaded, {dep.n_flows} in the file")
    return n_rules


def drive_before_window(built, tr: dict, dep, seed: int, compiles: list,
                        say) -> list:
    """As the flow family's, with the rows' priority flags: each reachable
    fused depth as a backlog of the mix's own rows, in process."""
    depths = flow.reachable_depths(dep, tr, built.server)
    mix = Mix(tr, dep, seed, 991)
    cap = int(dep.spec["engine"]["batch_size"])
    for d in depths:
        cols = mix.frames(-(-d * cap // mix.frame_rows))
        n0 = len(compiles)
        built.service.request_batch_arrays(
            *[c.reshape(-1)[:d * cap] for c in cols])
        say(f"warm-up: depth-{d} backlog of {d * cap} rows in process, "
            f"{len(compiles) - n0} compiles")
    return depths


progress = flow.progress


# -- the probe's sets ---------------------------------------------------------
class _Checks(flow._Checks):
    """The flow family's checks on this family's frames, and the five of
    the shaping arms."""

    def __init__(self, p):
        super().__init__(p)  # the flow family's flows, and its guard's room
        self.ref = shaped_reference.for_deployment(p.dep)
        if self.frame_rows < 2 * int(self.flows["occupy"][1]) + 2:
            raise ValueError("the occupy check's second frame does not fit "
                             f"a frame of {self.frame_rows} rows")

    def _send(self, ids, acq, prio=None):
        prio = np.zeros(len(ids), np.uint8) if prio is None else prio
        return self.p.send(np.asarray(ids, np.int64),
                           np.asarray(acq, np.int32),
                           np.asarray(prio, np.uint8))

    @staticmethod
    def _bad(got, want) -> int:
        """Rows whose status differs, and SHOULD_WAIT rows whose wait does."""
        (status, wait), (want_s, want_w) = got, want
        want_s = np.asarray(want_s, np.int8)
        waits = (status == SHOULD_WAIT) & (want_s == SHOULD_WAIT)
        return int((status != want_s).sum()) + int(
            (wait[waits] != np.asarray(want_w, np.int32)[waits]).sum())

    def _one_frame(self, name: str, fid: int, n: int) -> None:
        """``n`` one-token rows of one flow, decided at one instant."""
        if self.single:
            self.say(f"probe {name}: skipped, one-token frames arrive at "
                     f"different times")
            return
        ids, acq = np.full(n, fid, np.int64), np.ones(n, np.int32)
        status, wait, took = self._send(ids, acq)
        self._record(name, n, self._bad((status, wait), self._want(ids, acq)),
                     took, f"; {int((status == OK).sum())} OK, "
                     f"{int((status == SHOULD_WAIT).sum())} SHOULD_WAIT")

    def warm(self) -> None:
        self._one_frame("warm", self.flows["warm"][0], 60)

    def warm_paced(self) -> None:
        self._one_frame("warm_paced", self.flows["warm_paced"][0], 30)

    def _small_reference(self, *fids):
        """A reference of its own over the flows ``fids``."""
        e = self.dep.spec["engine"]
        return shaped_reference.Reference(
            self.dep.reference_rules(only=set(fids)), self.dep.ns_max_qps,
            int(e["bucket_ms"]), int(e["n_buckets"]),
            occupy_timeout_ms=self.dep.occupy_timeout_ms)

    def warm_slide(self) -> None:
        if self.single:
            return
        fid = self.flows["warm_slide"][0]
        frames = [np.full(n, fid, np.int64) for n in (60, 5, 5, 5, 5)]
        got, at, took = [], [], 0.0
        t0 = time.monotonic()
        for k, ids in enumerate(frames):
            pause = t0 + 0.2 * k - time.monotonic()
            if pause > 0:
                time.sleep(pause)
            at.append(int((time.monotonic() - t0) * 1000))
            status, wait, t = self._send(ids, np.ones(len(ids), np.int32))
            got.append((status, wait))
            took += t
        # the server's next second begins before frame ``k`` (1 .. 4), or
        # after the last of them (5): the first frame is put so that it does
        best = None
        for k in range(1, len(frames) + 1):
            edge = at[k] if k < len(at) else at[-1] + 200
            first = 11_000 - (at[k - 1] + edge) // 2
            ref = self._small_reference(fid)
            bad = sum(self._bad(g, ref.decide_frame(
                first + a, ids, [1] * len(ids)))
                for g, a, ids in zip(got, at, frames))
            if best is None or bad < best[0]:
                best = (bad, k)
        n_ok = [int((s == OK).sum()) for s, _w in got]
        self._record("warm_slide", sum(len(f) for f in frames), best[0],
                     took, f"; OK by frame {n_ok}, sent at {at} ms, the "
                     f"second taken to begin before frame {best[1]}")

    def occupy(self) -> None:
        """``occupy`` and ``occupy_mature`` (see the module's head)."""
        if self.single:
            return
        (fid, count), (clock, tick) = self.flows["occupy"], self.flows["clock"]
        c, dep = int(count), self.dep
        prio2 = (self.rng.random(2 * c) < 0.6).astype(np.uint8)
        frames = [  # (seconds after the frame before, ids, prioritized)
            (0.0, [fid] * (c + 8) + [clock], [0] * c + [1, 0] * 4 + [0]),
            # when the bucket of the first frame is the window's oldest
            ((dep.window_ms - dep.bucket_ms) / 1000.0,
             [fid] * (2 * c) + [clock], list(prio2) + [0]),
            # past the start of the next bucket, where the borrows count
            (2.5 * dep.bucket_ms / 1000.0, [fid] * 8, [1, 0] * 4)]
        got, gaps, took, last = [], [], 0.0, None
        for pause, ids, prio in frames:
            if last is not None:
                time.sleep(max(0.0, last + pause - time.monotonic()))
                gaps.append(int((time.monotonic() - last) * 1000))
            last = time.monotonic()
            status, wait, t = self._send(ids, np.ones(len(ids), np.int32),
                                         prio)
            got.append((status, wait))
            took += t
        # the server's own time between the first two frames, if the clock
        # flow's second grant says it: its wait is its cost less the time
        # gone by
        if got[1][0][-1] == SHOULD_WAIT:
            gaps[0] = round(1000.0 / tick) - int(got[1][1][-1])
        best = None
        for phase in range(dep.bucket_ms):
            ref = self._small_reference(fid, clock)
            t, bad = 10_000 + phase, []
            for k, (_p, ids, prio) in enumerate(frames):
                t += gaps[k - 1] if k else 0
                bad.append(self._bad(got[k], ref.decide_frame(
                    t, ids, [1] * len(ids), prio)))
            if best is None or sum(bad) < sum(best[0]):
                best = (bad, phase)
        bad, phase = best
        n_wait = [int((s == SHOULD_WAIT).sum()) for s, _w in got]
        note = (f"; SHOULD_WAIT by frame {n_wait}, frames {gaps} ms apart, "
                f"the first taken to fall {phase} ms into its bucket")
        self._record("occupy", len(frames[0][1]) + len(frames[1][1]),
                     bad[0] + bad[1], took, note)
        self._record("occupy_mature", len(frames[2][1]), bad[2], took)


def probe_checks(p) -> list:
    """The checks of one probe, in the order they run."""
    c = _Checks(p)
    return [c.tight, c.big, c.guard, c.paced, c.warm, c.warm_slide,
            c.warm_paced, c.occupy]


# -- the controls of control.py -----------------------------------------------
def unshaped(service):
    """The service with the warm-up taken out of its rules: every WARM_UP
    rule loaded again as DEFAULT and every WARM_UP_RATE_LIMITER rule as
    RATE_LIMITER, so a cold flow admits its whole count. (``server.build``
    wraps the service once the rules are loaded and before the door
    starts; the rule table is data, nothing compiles again.)"""
    from dataclasses import replace

    plain = {WARM_UP: DEFAULT, WARM_UP_RATE_LIMITER: RATE_LIMITER}
    service.load_rules([
        replace(r, control_behavior=plain.get(int(r.control_behavior),
                                              r.control_behavior))
        for r in service.current_rules()])
    return service


CONTROLS = {"over_admit": flow.OverAdmit, "unshaped": unshaped}
