"""The flow family: a deployment that is a table of ``ClusterFlowRule``s,
asked with FLOW and BATCH_FLOW frames. The first family, and the one every
configuration without a ``"family"`` key belongs to. ``families/__init__.py``
lists what a family owns; this module is that list for flow tables.

Layout. Plain flows have ids ``0 .. n_plain-1`` and flow ``i`` belongs to
namespace ``ns{i % namespaces}``; inside a namespace a flow's popularity rank
is ``i // namespaces``. The hottest ranks of every namespace are metered at
the finite counts ``rules.metered_counts`` (rank 0 first); every other plain
flow carries ``rules.unmetered_count``, which no traffic reaches. The probe's
flows have ids from ``PROBE_BASE`` up and live in the first probe namespace;
the second probe namespace is kept idle for the namespace-guard check. No
traffic mix touches a probe namespace.

A row is ``(flow_id, acquire)``; the generators carry rows as the two columns
``(ids, acq)``. Mix parameters of this family:

    tenants     {"popularity": "zipf"|"uniform", "theta": t}: the namespace a
                frame's rows belong to (one tenant's sidecar sends a frame)
    flows       {"dist": "zipf"|"uniform", "theta": t}: a row's flow by its
                popularity rank inside the tenant
    acquire     {"values": [...], "weights": [...]}: tokens a row asks for

Every seed gives the same multiset of frame sizes, arrival times and
tenant frames; the seed permutes which tenant sends when and draws the flows
and the acquires.

The probe's checks (every comparison has the limit 0 mismatches):

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

import os
import sys

import numpy as np

from cellbench import traffic, wire
# every status is re-exported: the tests name them through this module
from cellbench.deploy import (BLOCKED, DECIDED, DEFAULT, DEGRADED,  # noqa: F401
                              FAIL, MOVED, NO_RULE, OK, OVERLOAD,
                              RATE_LIMITER, SHOULD_WAIT, STANDBY, TOO_MANY,
                              load_json)
from cellbench.families import flow_reference as reference

PROBE_BASE = 1_000_000
CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")
MAX_CHECK_S = 0.85  # a check slower than this has left its window

# what the generators speak: FLOW and BATCH_FLOW, as ``wire.py`` writes them
encode_batch = wire.encode_batch
encode_singles = wire.encode_singles
SINGLE_REPLIES = ((wire.FLOW,), wire.SINGLE_RSP)
BATCH_REPLIES = ((wire.BATCH_FLOW,), wire.RSP_ROW)
MAX_ROWS_PER_FRAME = wire.MAX_ROWS_PER_FRAME


class Deployment:
    def __init__(self, spec: dict):
        self.spec = spec
        self.name = spec["name"]
        r = spec["rules"]
        self.namespaces = int(r["namespaces"])
        self.n_flows = int(r["n_flows"])
        self.unmetered_count = float(r["unmetered_count"])
        self.metered_counts = [float(c) for c in r["metered_counts"]]
        self.probe_namespaces = [int(n) for n in r["probe_namespaces"]]
        self.probe = r["probe"]
        self.ns_max_qps = float(spec["ns_max_qps"])
        self.window_ms = (int(spec["engine"]["bucket_ms"])
                          * int(spec["engine"]["n_buckets"]))
        self.bucket_ms = int(spec["engine"]["bucket_ms"])
        self.probe_rules = self._probe_rules()
        self.n_plain = self.n_flows - len(self.probe_rules)
        if self.n_plain < self.namespaces * (len(self.metered_counts) + 1):
            raise ValueError("too few plain flows for the metered ranks")

    # -- plain flows -------------------------------------------------------
    def traffic_namespaces(self) -> list:
        return [n for n in range(self.namespaces)
                if n not in self.probe_namespaces]

    def flows_per_namespace(self) -> int:
        """Ranks every namespace has (the last, ragged rank is left out)."""
        return self.n_plain // self.namespaces

    def flow_id(self, ns, rank):
        return np.asarray(ns, np.int64) + self.namespaces * np.asarray(
            rank, np.int64)

    def is_metered(self, flow_ids) -> np.ndarray:
        f = np.asarray(flow_ids, np.int64)
        return (f < PROBE_BASE) & (f // self.namespaces
                                   < len(self.metered_counts))

    def metered_index(self, flow_ids) -> np.ndarray:
        """Dense index of a metered plain flow: ``ns * n_ranks + rank``."""
        f = np.asarray(flow_ids, np.int64)
        return (f % self.namespaces) * len(self.metered_counts) + (
            f // self.namespaces)

    def metered_count_of_index(self) -> np.ndarray:
        return np.tile(np.asarray(self.metered_counts),
                       self.namespaces)

    # -- the ledger's view of a row ------------------------------------------
    def ledger_counts(self) -> np.ndarray:
        """The count of every key under which admitted tokens are summed:
        the metered plain flows, by ``metered_index``."""
        return self.metered_count_of_index()

    def ledger_view(self, cols, st, remaining):
        """What the rows ``cols`` that came back with statuses ``st`` are to
        the ledger: ``(decided, brownout pass, rows that can never be,
        ledger keys, tokens admitted under each key)``. A brownout pass is OK
        with ``remaining == 0`` on an unmetered flow; an unmetered row
        BLOCKED can never be."""
        ids, acq = cols
        metered = self.is_metered(ids)
        brown = (st == OK) & (remaining == 0) & ~metered
        ok_m = metered & (st == OK)
        return (DECIDED[st], brown, int(((st == BLOCKED) & ~metered).sum()),
                self.metered_index(ids[ok_m]), acq[ok_m])

    def window_checks(self, client: dict) -> list:
        """``(what, got, limit)`` of this family's counts over a window."""
        return [("unmetered rows BLOCKED", client["never_rows"], 0)]

    # -- probe flows -------------------------------------------------------
    def _probe_rules(self) -> list:
        """``(flow_id, count, behaviour, role)`` of the probe's own flows."""
        p = self.probe
        out = []
        fid = PROBE_BASE
        for _set in range(int(p["sets"])):
            for c in p["tight_counts"]:
                out.append((fid, float(c), DEFAULT, "tight"))
                fid += 1
            out.append((fid, float(p["big_count"]), DEFAULT, "big"))
            fid += 1
            out.append((fid, float(p["paced_count"]), RATE_LIMITER, "paced"))
            fid += 1
        return out

    def probe_set(self, k: int) -> dict:
        per = len(self.probe["tight_counts"]) + 2
        rules = self.probe_rules[k * per:(k + 1) * per]
        return {
            "tight": [(f, c) for f, c, _b, role in rules if role == "tight"],
            "big": next((f, c) for f, c, _b, role in rules if role == "big"),
            "paced": next((f, c) for f, c, _b, role in rules
                          if role == "paced"),
        }

    def rules(self):
        """Every rule as ``(flow_id, count, namespace_name, behaviour)``."""
        nm = len(self.metered_counts)
        for i in range(self.n_plain):
            rank = i // self.namespaces
            count = (self.metered_counts[rank] if rank < nm
                     else self.unmetered_count)
            yield i, count, f"ns{i % self.namespaces}", DEFAULT
        ns = f"ns{self.probe_namespaces[0]}"
        for fid, count, behaviour, _role in self.probe_rules:
            yield fid, count, ns, behaviour


# ``deploy.load`` names a deployment's family; one built here directly (the
# tests do) is a flow table's all the same
Deployment.family = sys.modules[__name__]


def load_deployment(name: str) -> Deployment:
    return Deployment(load_json(os.path.join(CONFIGS, name + ".json")))


# -- the generator's side: drawing rows ---------------------------------------
class Mix:
    """Draws frames of one traffic mix over one deployment."""

    def __init__(self, tr: dict, deployment, seed: int, salt: int):
        self.t = tr
        self.d = deployment
        self.rng = np.random.default_rng([int(seed), int(salt)])
        self.frame_rows = 1 if tr["msg"] == "single" else int(
            tr["frame_rows"])
        self.tenants = np.asarray(deployment.traffic_namespaces(), np.int64)
        tp = tr["tenants"]
        self.tenant_p = traffic.pmf(tp["popularity"], len(self.tenants),
                                    tp.get("theta", 0.0))
        fl = tr["flows"]
        self.flow_cdf = np.cumsum(traffic.pmf(
            fl["dist"], deployment.flows_per_namespace(),
            fl.get("theta", 0.0)))
        acq = tr["acquire"]
        self.acq_values = np.asarray(acq["values"], np.int32)
        w = np.asarray(acq["weights"], np.float64)
        self.acq_cdf = np.cumsum(w / w.sum())
        self.uniform_acquire = len(self.acq_values) == 1

    def frame_tenants(self, n_frames: int) -> np.ndarray:
        counts = traffic.apportion(self.tenant_p, n_frames)
        who = np.repeat(self.tenants, counts)
        self.rng.shuffle(who)
        return who

    def rows(self, frame_tenants: np.ndarray):
        """``(flow_ids, acquires)`` as ``[n_frames, frame_rows]`` arrays."""
        shape = (len(frame_tenants), self.frame_rows)
        rank = np.searchsorted(self.flow_cdf, self.rng.random(shape))
        rank = np.minimum(rank, len(self.flow_cdf) - 1)
        ids = self.d.flow_id(frame_tenants[:, None], rank)
        if self.uniform_acquire:
            acq = np.full(shape, self.acq_values[0], np.int32)
        else:
            at = np.searchsorted(self.acq_cdf, self.rng.random(shape))
            acq = self.acq_values[np.minimum(at, len(self.acq_values) - 1)]
        return ids, acq

    def frames(self, n_frames: int):
        return self.rows(self.frame_tenants(n_frames))


# -- the program's side: what server.build hands to the public doors ----------
def service_args(dep) -> dict:
    """Constructor arguments of ``DefaultTokenService`` the file states."""
    return {}


def load_rules(service, dep) -> int:
    from sentinel_tpu.engine import ClusterFlowRule
    from sentinel_tpu.engine.rules import ThresholdMode

    service.load_rules(
        [ClusterFlowRule(fid, count, ThresholdMode.GLOBAL, ns,
                         control_behavior=behaviour)
         for fid, count, ns, behaviour in dep.rules()],
        ns_max_qps=dep.ns_max_qps,
    )
    n_rules = len(service.current_rules())
    if n_rules != dep.n_flows:
        raise RuntimeError(f"{n_rules} rules loaded, {dep.n_flows} in the file")
    return n_rules


def reachable_depths(dep, tr: dict, server) -> list:
    """Fusion-ladder depths a dispatch through this door can reach with this
    mix: the lane folds up to ``fuse_depth`` pulls of ``max_batch`` rows, and
    the mix cannot have more rows in flight than its own cap."""
    most = min(server.fuse_depth * server.max_batch,
               traffic.reachable_rows(tr))
    full = most // int(dep.spec["engine"]["batch_size"])
    return sorted(d for d in dep.spec["fuse_depths"] if d <= full)


def drive_before_window(built, tr: dict, dep, seed: int, compiles: list,
                        say) -> list:
    """In process, before the window: each reachable fused depth with the
    mix's own acquires. Returns the depths driven, which the warm-up then
    expects among the lane's dispatches."""
    depths = reachable_depths(dep, tr, built.server)
    mix = Mix(tr, dep, seed, 991)
    cap = int(dep.spec["engine"]["batch_size"])
    rows = mix.frame_rows
    for d in depths:
        # a full-depth backlog of the mix's own rows, as one call of the
        # service's public entry: what the lane hands over after a stall
        ids, acq = mix.frames(-(-d * cap // rows))
        n0 = len(compiles)
        built.service.request_batch_arrays(
            ids.reshape(-1)[:d * cap], acq.reshape(-1)[:d * cap])
        say(f"warm-up: depth-{d} backlog of {d * cap} rows in process, "
            f"{len(compiles) - n0} compiles")
    return depths


def progress(built):
    """What the stall watch expects to keep rising: dispatches whose
    verdicts the reply lanes have materialized."""
    from sentinel_tpu.metrics.server import server_metrics

    done = server_metrics().decide_ms
    return lambda: done.count


# -- the probe's sets ---------------------------------------------------------
class _Checks:
    """The flow family's checks, run by ``probe.Probe`` one after another."""

    def __init__(self, p):
        self.p, self.dep, self.rng, self.say = p, p.dep, p.rng, p.say
        self.single, self.frame_rows = p.single, p.frame_rows
        self._record = p.record
        self.flows = p.dep.probe_set(p.probe_set)
        self.acq_values = [int(a) for a in p.tr["acquire"]["values"]]
        self.ref = reference.for_deployment(p.dep)
        # The reference decides the whole probe at one instant: every check
        # has flows of its own, and the probe namespace's guard window sees
        # all of them, as the server's does while the probe lasts under one
        # window. That holds only while the probe's rows fit the guard.
        n_rows = (2 * sum(c for _f, c in self.flows["tight"]) + 2048
                  + int(self.flows["big"][1]) + 1000 + 60)
        if n_rows >= p.dep.ns_max_qps * p.dep.window_ms / 1000:
            raise ValueError(f"the probe's {n_rows} rows do not fit the "
                             f"namespace guard of {p.dep.ns_max_qps}/s")

    def _background(self, ns: int, n: int):
        """Unmetered plain flows of a probe namespace, the mix's acquires."""
        lo = len(self.dep.metered_counts)
        rank = self.rng.integers(lo, self.dep.flows_per_namespace(), size=n)
        acq = self.rng.choice(self.acq_values, size=n)
        return self.dep.flow_id(ns, rank), acq.astype(np.int32)

    def _send(self, ids, acq):
        return self.p.send(np.asarray(ids, np.int64),
                           np.asarray(acq, np.int32))

    def _want(self, ids, acq):
        """The reference's verdicts for rows that arrive together."""
        return self.ref.decide_frame(10_000, ids, acq)

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
                     f"; {int((status == OK).sum())} OK of {n}, "
                     f"count {int(count)}")

    def guard(self) -> None:
        ns = self.dep.probe_namespaces[1]
        n = int(self.dep.ns_max_qps * self.dep.window_ms / 1000) + 2768
        ids, _ = self._background(ns, n)
        acq = np.ones(n, np.int32)
        status, _wait, took = self._send(ids, acq)
        n_ok = int((status == OK).sum())
        n_many = int((status == TOO_MANY).sum())
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
        waits = status == SHOULD_WAIT
        bad += int((wait[waits] != np.asarray(want_w, np.int32)[waits]).sum())
        self._record("paced", 60, bad, took)


def probe_checks(p) -> list:
    """The checks of one probe, in the order they run."""
    c = _Checks(p)
    return [c.tight, c.big, c.guard, c.paced]


# -- the controls of control.py -----------------------------------------------
class OverAdmit:
    """The service with one answer altered where it is produced: the first
    BLOCKED verdict of every dispatch comes back OK."""

    def __init__(self, service):
        self._service = service

    def __getattr__(self, name):
        return getattr(self._service, name)

    def dispatch_batch_arrays(self, ids, acq=None, prios=None):
        mat = self._service.dispatch_batch_arrays(ids, acq, prios)

        def altered():
            status, remaining, wait = mat()
            blocked = (status == BLOCKED).nonzero()[0]
            if blocked.size:
                status = status.copy()
                status[blocked[0]] = OK
            return status, remaining, wait
        return altered

    def request_batch_arrays(self, ids, acq=None, prios=None):
        return self.dispatch_batch_arrays(ids, acq, prios)()


CONTROLS = {"over_admit": OverAdmit}
