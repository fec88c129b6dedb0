"""The flow + hot-parameter family: a deployment whose resources carry a
``ClusterFlowRule`` and, the most popular of them, a ``ClusterParamFlowRule``
as well, on one token server. Upstream's own cluster demo wires both rule
suppliers for one namespace, and a call on a resource with both rules asks
the server twice: ``requestParamToken`` for its caller (``ParamFlowSlot``,
order -3000), then, if that passed, ``requestToken`` for the resource
(``FlowSlot``, order -2000). Both lanes and both states are live at once.

It is built of the two families it joins and owns only what neither can
know: ``flow.py`` draws, meters and probes the flow rows, ``hotparam.py`` the
param rows, and this module the frame's kind, the call, and what living
together must not change.

Layout. The flow table is ``flow.py``'s (``rules``). The ``param_rules.ranks``
most popular flows of every traffic namespace carry a param rule, in the
flow's own namespace, whose id is ``param_rules.id_base`` + the flow's id: a
range of its own. A rule limits every caller to ``count`` tokens per window,
its ``hot_values`` most popular callers to ``hot_count``
(``ParamFlowItem``); a caller is known by its rank inside its rule and
travels as ``hotparam.value_hash`` of (the rule's index, rank), the rule's
index being ``rank of its flow * traffic namespaces + the namespace's
place``. The probe namespaces hold the flow family's probe flows, the
hotparam family's probe rules, and per probe set the rules of the three sets
below, with ids from ``EXTRA_BASE``; the new sets' flow rules take the place
of the last plain flows (the ragged last rank, which no traffic reaches), so
the table holds ``rules.n_flows`` rules in all.

A row is ``(kind, id, acquire, caller hashes[1])``: ``kind`` is the wire type
of the frame the row travels in, BATCH_FLOW (5) or BATCH_PARAM_FLOW (27); a
frame is of one kind; a flow row's hashes are unused. Mix parameters: the
flow family's (``tenants``, ``flows``, ``acquire``) for the flow frames, and

    param_frames   {"of_every": 4, "callers": {"dist", "theta"}, "acquire"}:
                   of every ``of_every`` consecutive frames one is a param
                   frame, and it rotates over the connections: frame k of a
                   generator goes to connection ``k mod connections``, and is
                   the param frame when ``(k div of_every + k mod of_every)
                   mod of_every == of_every - 1``. With as many connections
                   as ``of_every`` each connection's own sequence is
                   F F F P: a sidecar's two batchers, the flow batcher
                   filling three times as fast. A param frame is one
                   tenant's: its requests draw their flow from ``flows``
                   restricted to the ruled ranks, and a caller each

The ledger holds the flow family's metered flows, then the hotparam family's
metered (rule, caller) keys, then two keys that only count: the decided rows
of each kind.

The probe (every limit 0 mismatches but ``crowd``'s): the flow family's sets
and the hotparam family's, each on its own rules, and three more against
``flowparam_reference.py``:

    collide     a param rule and a flow rule with the same number, both
                tight. A param frame alone, then on one connection,
                pipelined, a flow frame, a param frame, a flow frame: each
                kind's verdicts are its own reference's, in frame order, and
                neither kind moved the other's count
    chain       seeded calls on six resources, four of them with a param
                rule, as a client makes them: the param frame of the calls
                on ruled resources, then the flow frame of the survivors
                (the calls the server passed, and those with no param rule);
                call by call against the call-level reference
    interleave  flow frames of the cell's size and param frames pipelined
                back to back on one connection without waiting, so that the
                device lane holds a pull of the other kind: every reply
                under its own xid, every verdict exact
"""

from __future__ import annotations

import math
import sys

import numpy as np

from cellbench import probe, traffic, wire
from cellbench.deploy import OK
from cellbench.families import flow, flowparam_reference, hotparam

FLOW, PARAM = wire.BATCH_FLOW, hotparam.BATCH_PARAM_FLOW
EXTRA_BASE = 1_100_000  # the three new sets' rules, flow and param alike
EXTRA_STRIDE = 100  # ids a probe set may take from EXTRA_BASE
_COUNTED_ONLY = 1e18  # the count of a ledger key that only counts rows

SINGLE_REPLIES = ((), wire.SINGLE_RSP)  # batch frames only
BATCH_REPLIES = ((FLOW, PARAM), wire.RSP_ROW)
MAX_ROWS_PER_FRAME = min(flow.MAX_ROWS_PER_FRAME, hotparam.MAX_ROWS_PER_FRAME)


def encode_batch(xid: int, kind, ids, acquires, hashes) -> bytes:
    """One frame of the kind its rows name: BATCH_FLOW of ``(ids,
    acquires)`` or BATCH_PARAM_FLOW of ``(ids, acquires, hashes)``."""
    k = int(kind[0])
    if (np.asarray(kind) != k).any():
        raise ValueError("a frame is of one kind")
    if k == PARAM:
        return hotparam.encode_batch(xid, ids, acquires, hashes)
    if k == FLOW:
        return wire.encode_batch(xid, ids, acquires)
    raise ValueError(f"no frame of kind {k}")


def encode_singles(first_xid: int, *cols):
    raise ValueError("the flowparam family sends batch frames only")


def _kinds(n: int, kind: int) -> np.ndarray:
    return np.full(n, kind, np.int8)


def _no_hashes(n: int) -> np.ndarray:
    return np.zeros((n, 1), np.int64)


class Deployment:
    def __init__(self, spec: dict):
        self.spec = spec
        self.name = spec["name"]
        pr = spec["param_rules"]
        self.probe = pr["probe"]
        self.probe_sets = int(pr["probe_sets"])
        self._per_set = 2 + len(self.probe["chain_flow_counts"])
        if self._per_set > EXTRA_STRIDE:
            raise ValueError("too many chain resources for a probe set's ids")
        # the new sets' flow rules take the place of the last plain flows
        n_extra = self.probe_sets * self._per_set
        self.flow = flow.Deployment(dict(spec, rules=dict(
            spec["rules"], n_flows=int(spec["rules"]["n_flows"]) - n_extra)))
        self.tenants = np.asarray(self.flow.traffic_namespaces(), np.int64)
        self.ranks = int(pr["ranks"])
        self.id_base = int(pr["id_base"])
        self.n_rules = self.ranks * len(self.tenants)
        if self.n_rules != int(pr["n_rules"]):
            raise ValueError(f"{self.ranks} ranks of {len(self.tenants)} "
                             f"namespaces are not {pr['n_rules']} rules")
        if self.ranks > self.flow.flows_per_namespace():
            raise ValueError("more ruled ranks than a namespace has flows")
        self.param = hotparam.Deployment(dict(spec, rules=pr))
        self._place = np.full(self.flow.namespaces, -1, np.int64)
        self._place[self.tenants] = np.arange(len(self.tenants))
        # one geometry for the harness's invariant over both kinds of key:
        # the looser of the two (a span of replies inside the shorter
        # window, held to one count, is sound for either); the flow keys
        # are held to their own window as well (``window_checks``)
        self.window_ms = max(self.flow.window_ms, self.param.window_ms)
        self.bucket_ms = max(self.flow.bucket_ms, self.param.bucket_ms)
        self._n_flow_keys = len(self.flow.ledger_counts())
        self._n_keys = self._n_flow_keys + len(self.param.ledger_counts())

    # -- which flows carry a param rule --------------------------------------
    def param_index(self, ns, rank) -> np.ndarray:
        """The index (``0 .. n_rules-1``) of the param rule on the flow of
        rank ``rank`` of traffic namespace ``ns``."""
        return np.asarray(rank, np.int64) * len(self.tenants) + self._place[
            np.asarray(ns, np.int64)]

    def param_id(self, flow_ids) -> np.ndarray:
        """The id of the param rule on a flow."""
        return self.id_base + np.asarray(flow_ids, np.int64)

    def _flow_of_index(self, index: int) -> int:
        return int(self.flow.flow_id(self.tenants[index % len(self.tenants)],
                                     index // len(self.tenants)))

    def _probe_ns(self, k: int = 0) -> str:
        ns = self.flow.probe_namespaces
        return f"ns{ns[k % len(ns)]}"

    def extra(self, k: int) -> dict:
        """The rules of probe set ``k``'s three new sets, as ``(flow id, flow
        count, param rule id or None)``."""
        p = self.probe
        base = EXTRA_BASE + EXTRA_STRIDE * k
        chain = [(base + 1 + i, float(c),
                  int(self.param_id(base + 1 + i))
                  if i < int(p["chain_param_resources"]) else None)
                 for i, c in enumerate(p["chain_flow_counts"])]
        last = base + self._per_set - 1
        return {"collide": (base, float(p["collide_flow_count"]), base),
                "chain": chain,
                "interleave": (last, float(p["interleave_flow_count"]), last)}

    def _extras(self):
        for k in range(self.probe_sets):
            e = self.extra(k)
            yield k, [e["collide"], *e["chain"], e["interleave"]]

    def flow_rules(self):
        """Every flow rule as ``(flow id, count, namespace name,
        behaviour)``: the flow family's, and the new sets'."""
        yield from self.flow.rules()
        for _k, rules in self._extras():
            for fid, count, _rule in rules:
                yield fid, count, self._probe_ns(), flow.DEFAULT

    def param_rules(self):
        """Every param rule as ``(rule id, count, ((caller hash, threshold),
        ...), namespace name)``: the traffic rules in their flows'
        namespaces, the hotparam family's probe rules, and the new sets'
        (a chain's first resource gives its caller 0 an item)."""
        per = hotparam.PROBE_RULES_PER_SET
        for r, count, items in self.param.rules():
            if r < hotparam.PROBE_BASE:
                fid = self._flow_of_index(r)
                yield (int(self.param_id(fid)), count, items,
                       f"ns{fid % self.flow.namespaces}")
            else:
                yield r, count, items, self._probe_ns(
                    (r - hotparam.PROBE_BASE) // per)
        for k, rules in self._extras():
            first_chain = rules[1][2]
            for _fid, _count, rule in rules:
                if rule is None:
                    continue
                items = (((int(hotparam.value_hash(rule, 0)),
                           self.param.hot_count),)
                         if rule == first_chain else ())
                yield rule, self.param.count, items, self._probe_ns(k)

    def param_rule_of(self) -> dict:
        """``{flow id: its param rule's id}`` of every resource a call on
        which asks twice: the ruled traffic flows and the chains'."""
        out = {}
        for r in range(self.n_rules):
            fid = self._flow_of_index(r)
            out[fid] = int(self.param_id(fid))
        for _k, rules in self._extras():
            out.update((fid, rule) for fid, _c, rule in rules[1:-1]
                       if rule is not None)
        return out

    # -- the ledger's view of a row ------------------------------------------
    def ledger_counts(self) -> np.ndarray:
        """The flow family's keys, the hotparam family's, and two that only
        count the decided rows of each kind."""
        return np.concatenate([self.flow.ledger_counts(),
                               self.param.ledger_counts(),
                               [_COUNTED_ONLY, _COUNTED_ONLY]])

    def ledger_view(self, cols, st, remaining):
        kind, ids, acq, hashes = cols
        is_param = np.asarray(kind) == PARAM
        decided = np.zeros(len(st), bool)
        brown = np.zeros(len(st), bool)
        never, keys, tokens = 0, [], []
        for mask, side, side_cols, first_key in (
                (~is_param, self.flow, (ids, acq), 0),
                (is_param, self.param, (ids, acq, hashes),
                 self._n_flow_keys)):
            if not mask.any():
                continue
            d, b, n, k, t = side.ledger_view(
                [c[mask] for c in side_cols], st[mask], remaining[mask])
            decided[mask], brown[mask] = d, b
            never += n
            keys.append(np.asarray(k, np.int64) + first_key)
            tokens.append(np.asarray(t, np.float64))
        good = decided & ~brown
        keys.append(np.array([self._n_keys, self._n_keys + 1]))
        tokens.append(np.array([(good & ~is_param).sum(),
                                (good & is_param).sum()], np.float64))
        return (decided, brown, never, np.concatenate(keys),
                np.concatenate(tokens))

    def window_checks(self, client: dict) -> list:
        """Both families' checks (one count of rows that can never be: an
        unmetered flow row BLOCKED, a param request answered NO_RULE), the
        share of param rows among decided rows, and the flow keys held to
        the flow window's own span."""
        out = [("unmetered flow rows BLOCKED or param requests NO_RULE",
                client["never_rows"], 0)]
        adm = client.get("admitted")
        if adm is None or not adm.size:
            return out
        rows_flow, rows_param = adm[self._n_keys].sum(), adm[
            self._n_keys + 1].sum()
        share = 100.0 * rows_param / max(1.0, rows_flow + rows_param)
        out.append(("param rows share of decided rows off 25 percent in "
                    "hundredths of a point",
                    int(round(abs(share - 25.0) * 100)), 100))
        worst = _admitted_over_count(
            adm[:self._n_flow_keys], client["lat_max"],
            self.flow.ledger_counts(), self.flow.window_ms,
            self.flow.bucket_ms)
        out.append(("flow tokens admitted per flow window over count in "
                    "millionths", int(math.ceil(worst * 1e6)), 1_000_000))
        return out


Deployment.family = sys.modules[__name__]

_BIN_S = 0.1  # loadgen.BIN_S: the admitted-token ledger's bins
_USUAL_REPLY_S = 0.2  # run.USUAL_REPLY_S


def _admitted_over_count(adm, lat_max, counts, window_ms: int,
                         bucket_ms: int) -> float:
    """``run.window_invariants``'s ratio for keys of one window geometry:
    tokens admitted in any span of replies one shortest window less a usual
    reply long, over the count times the windows that span can touch given
    its slowest reply; the worst over keys and spans."""
    shortest = (window_ms - bucket_ms) / 1000.0
    k = max(1, int(round((shortest - _USUAL_REPLY_S) / _BIN_S)))
    c = np.cumsum(np.pad(adm, ((0, 0), (k, 0))), axis=1)
    most = c[:, k:] - c[:, :-k]
    lm = np.pad(lat_max, (k - 1, 0))
    slowest = np.max(np.stack([lm[i:i + adm.shape[1]] for i in range(k)]),
                     axis=0)
    windows = np.ceil((k * _BIN_S + slowest) / shortest - 1e-9)
    allowed = counts[:, None] * window_ms / 1000.0 * windows[None, :]
    return float((most / allowed).max())


# -- the generator's side: drawing rows ---------------------------------------
class Mix:
    """Draws frames of one traffic mix over one deployment: flow frames by
    the flow family's ``Mix``, and of every ``of_every`` frames one param
    frame, by position."""

    def __init__(self, tr: dict, deployment, seed: int, salt: int):
        self.d = deployment
        self.flow = flow.Mix(tr, deployment.flow, seed, salt)
        self.rng = np.random.default_rng([int(seed), int(salt), PARAM])
        self.frame_rows = self.flow.frame_rows
        pf = tr["param_frames"]
        self.of_every = int(pf["of_every"])
        self.acquire = int(pf["acquire"])
        fl = tr["flows"]
        ranked = traffic.pmf(fl["dist"],
                             deployment.flow.flows_per_namespace(),
                             fl.get("theta", 0.0))[:deployment.ranks]
        self.rank_cdf = np.cumsum(ranked / ranked.sum())
        cp = pf["callers"]
        self.caller_cdf = np.cumsum(traffic.pmf(
            cp["dist"], deployment.param.values_per_rule,
            cp.get("theta", 0.0)))

    def frame_tenants(self, n_frames: int) -> np.ndarray:
        return self.flow.frame_tenants(n_frames)

    def is_param(self, n_frames: int) -> np.ndarray:
        k, m = np.arange(n_frames), self.of_every
        return (k // m + k % m) % m == m - 1

    def _draw(self, cdf, shape) -> np.ndarray:
        return np.minimum(np.searchsorted(cdf, self.rng.random(shape)),
                          len(cdf) - 1)

    def param_rows(self, frame_tenants: np.ndarray):
        """``(rule ids, acquires, caller hashes [.., 1])`` of param frames,
        one tenant each."""
        shape = (len(frame_tenants), self.frame_rows)
        rank = self._draw(self.rank_cdf, shape)
        ns = np.asarray(frame_tenants, np.int64)[:, None]
        caller = self._draw(self.caller_cdf, shape)
        return (self.d.param_id(self.d.flow.flow_id(ns, rank)),
                np.full(shape, self.acquire, np.int32),
                hotparam.value_hash(self.d.param_index(ns, rank),
                                    caller)[..., None])

    def rows(self, frame_tenants: np.ndarray):
        """``(kind, ids, acquires, hashes)`` as ``[n_frames, frame_rows]``
        arrays (hashes ``[.., 1]``)."""
        n = len(frame_tenants)
        par = self.is_param(n)
        kind = np.where(par, PARAM, FLOW).astype(np.int8)[:, None].repeat(
            self.frame_rows, axis=1)
        ids = np.empty((n, self.frame_rows), np.int64)
        acq = np.empty((n, self.frame_rows), np.int32)
        hashes = np.zeros((n, self.frame_rows, 1), np.int64)
        ids[~par], acq[~par] = self.flow.rows(frame_tenants[~par])
        ids[par], acq[par], hashes[par] = self.param_rows(frame_tenants[par])
        return kind, ids, acq, hashes

    def frames(self, n_frames: int):
        return self.rows(self.frame_tenants(n_frames))


# -- the program's side -------------------------------------------------------
service_args = hotparam.service_args  # the sketch's geometry: ``spec["param"]``


def load_rules(service, dep) -> int:
    """Both tables through the public entries; both counts checked."""
    from sentinel_tpu.cluster.token_service import ClusterParamFlowRule
    from sentinel_tpu.engine import ClusterFlowRule
    from sentinel_tpu.engine.rules import ThresholdMode

    service.load_rules(
        [ClusterFlowRule(fid, count, ThresholdMode.GLOBAL, ns,
                         control_behavior=behaviour)
         for fid, count, ns, behaviour in dep.flow_rules()],
        ns_max_qps=dep.flow.ns_max_qps)
    n_flow = len(service.current_rules())
    if n_flow != int(dep.spec["rules"]["n_flows"]):
        raise RuntimeError(f"{n_flow} flow rules loaded, "
                           f"{dep.spec['rules']['n_flows']} in the file")
    rules = [ClusterParamFlowRule(rule, count,
                                  item_thresholds=items or None,
                                  namespace=ns)
             for rule, count, items, ns in dep.param_rules()]
    service.load_param_rules(rules)
    n_param = len(service.current_param_rules())
    if n_param != len(rules):
        raise RuntimeError(f"{n_param} param rules loaded, {len(rules)} in "
                           f"the file")
    return n_flow + n_param


def drive_before_window(built, tr: dict, dep, seed: int, compiles: list,
                        say) -> list:
    """In process, before the window: every fused depth the flow frames can
    reach (the flow family's drive), then one batch of the mix's own param
    rows at every serve bucket on the live sketch."""
    depths = flow.drive_before_window(built, tr, dep.flow, seed, compiles,
                                      say)
    service = built.service
    kernel, reason = service.param_impl()
    say(f"param path: impl {service.param_config.impl!r} resolved to "
        f"{kernel!r} ({reason})")
    mix = Mix(tr, dep, seed, 991)
    for bucket in dep.spec["serve_buckets"]:
        n = int(bucket)
        who = mix.frame_tenants(-(-n // mix.frame_rows))
        rules, acq, hashes = mix.param_rows(who)
        n0 = len(compiles)
        service.request_params_batch(
            rules.reshape(-1)[:n], acq.reshape(-1)[:n],
            hashes.reshape(-1, 1)[:n])
        say(f"warm-up: {n} param requests in process (bucket {bucket}), "
            f"{len(compiles) - n0} compiles")
    return depths


progress = flow.progress  # dispatches read, of both kinds: ``decide_ms``


# -- the probe's sets ---------------------------------------------------------
class _Side:
    """The probe as one of the two sibling families' checks see it: their
    own deployment, and rows in their own columns."""

    def __init__(self, p, dep, kind: int):
        self._p, self.dep, self._kind = p, dep, kind

    def __getattr__(self, name):
        return getattr(self._p, name)

    def send(self, ids, acq, hashes=None):
        n = len(ids)
        return self._p.send(_kinds(n, self._kind), ids, acq,
                            _no_hashes(n) if hashes is None else hashes)


class _Checks:
    """The three sets only this family has, each against a fresh call-level
    reference at one instant."""

    def __init__(self, p):
        self.p, self.dep, self.rng = p, p.dep, p.rng
        self.rules = p.dep.extra(p.probe_set)
        self.rows = p.frame_rows
        self.limit = int(p.dep.param.count)
        self.now = 10_000  # the reference's clock, ms

    def _frames(self, kind: int, ids, acq, hashes=None) -> list:
        """Rows of one kind as frames of at most the cell's size."""
        ids, acq = np.asarray(ids, np.int64), np.asarray(acq, np.int32)
        hashes = _no_hashes(len(ids)) if hashes is None else hashes
        return [(kind, ids[i:i + self.rows], acq[i:i + self.rows],
                 hashes[i:i + self.rows])
                for i in range(0, len(ids), self.rows)]

    def _exchange(self, frames: list):
        """``frames`` pipelined on one connection, in order, without
        waiting: ``(statuses of each frame, seconds)``."""
        p = self.p
        payloads = [encode_batch(p.xid + j, _kinds(len(ids), kind), ids, acq,
                                 hashes)
                    for j, (kind, ids, acq, hashes) in enumerate(frames)]
        p.xid += len(frames) + 16
        sizes = [len(f[1]) for f in frames]
        if not sizes:
            return [], 0.0
        status, _wait, took = probe.exchange(
            p.port, payloads, sum(sizes), False,
            (SINGLE_REPLIES, BATCH_REPLIES))
        return np.split(status, np.cumsum(sizes)[:-1]), took

    def _want(self, ref, frames: list) -> list:
        """The reference's statuses of ``frames``, each kind in its own
        frame order."""
        return [np.asarray(
            ref.param_frame(self.now, ids, acq, hashes) if kind == PARAM
            else ref.flow_frame(self.now, ids, acq), np.int8)
            for kind, ids, acq, hashes in frames]

    def _callers(self, rule: int, ranks) -> np.ndarray:
        return hotparam.value_hash(rule, np.asarray(ranks))[:, None]

    def _background(self, n: int):
        """Unmetered plain flows of the first probe namespace, acquire 1."""
        d = self.dep.flow
        rank = self.rng.integers(len(d.metered_counts),
                                 d.flows_per_namespace(), size=n)
        return d.flow_id(d.probe_namespaces[0], rank), np.ones(n, np.int32)

    def collide(self) -> None:
        fid, count, rule = self.rules["collide"]
        first = int(count) * 3 // 5

        def asked(callers, each):
            ranks = self.rng.permutation(np.repeat(callers, each))
            return self._frames(PARAM, np.full(len(ranks), rule),
                                np.ones(len(ranks)),
                                self._callers(rule, ranks))

        def flows(n):
            return self._frames(FLOW, np.full(n, fid), np.ones(n))

        alone = asked([0, 1, 2], self.limit + 3)
        mixed = (flows(first) + asked([3, 4], self.limit + 1)
                 + flows(int(count) - first + 10))
        got_a, took_a = self._exchange(alone)
        got_m, took_m = self._exchange(mixed)
        want = self._want(flowparam_reference.for_deployment(self.dep),
                          alone + mixed)
        got = np.concatenate(got_a + got_m)
        want = np.concatenate(want)
        self.p.record("collide", len(got), int((got != want).sum()),
                      took_a + took_m,
                      f"; {int((got == OK).sum())} OK, the reference "
                      f"{int((want == OK).sum())}")

    def chain(self) -> None:
        res = self.rules["chain"]
        pr = self.dep.probe
        n = int(pr["chain_calls"])
        which = self.rng.integers(0, len(res), n)
        caller = self.rng.integers(0, int(pr["chain_callers"]), n)
        fids = np.array([r[0] for r in res], np.int64)[which]
        rule = np.array([-1 if r[2] is None else r[2] for r in res],
                        np.int64)[which]
        acq = (1 + which % 2).astype(np.int32)  # one size a resource
        ruled = rule >= 0
        hashes = np.where(ruled, hotparam.value_hash(
            np.where(ruled, rule, 0), caller), 0)
        # as a client: the param frame of the calls on ruled resources ...
        asked, took_p = self._exchange(self._frames(
            PARAM, rule[ruled], acq[ruled], hashes[ruled][:, None]))
        got_p = np.full(n, -1, np.int64)
        got_p[ruled] = np.concatenate(asked)
        # ... then the flow frame of the calls that are left
        left = ~ruled | (got_p == OK)
        took, took_f = self._exchange(self._frames(FLOW, fids[left],
                                                   acq[left]))
        got_f = np.full(n, -1, np.int64)
        got_f[left] = np.concatenate(took)
        want = flowparam_reference.for_deployment(self.dep).calls(
            self.now, fids, acq, hashes)
        want_p = np.array([-1 if a is None else a for a, _f in want])
        want_f = np.array([-1 if f is None else f for _a, f in want])
        bad = int(((got_p != want_p) | (got_f != want_f)).sum())
        self.p.record("chain", n, bad, took_p + took_f,
                      f"; {int((got_p > OK).sum())} calls refused by their "
                      f"caller's rule, {int((got_f > OK).sum())} by the "
                      f"flow's")

    def interleave(self) -> None:
        fid, _count, rule = self.rules["interleave"]
        pr = self.dep.probe
        n_param = min(int(pr["interleave_param_rows"]), self.rows)
        frames = []
        for j in range(int(pr["interleave_frames"])):
            ids, acq = self._background(self.rows)
            ids[self.rng.choice(self.rows, self.rows // 4,
                                replace=False)] = fid
            frames += self._frames(FLOW, ids, acq)
            # an eighth of a param frame on four callers asked again and
            # again, the others asked once
            ranks = 1000 + j * n_param + np.arange(n_param)
            ranks[self.rng.choice(n_param, n_param // 8,
                                  replace=False)] = np.arange(
                                      n_param // 8) % 4
            frames += self._frames(PARAM, np.full(n_param, rule),
                                   np.ones(n_param),
                                   self._callers(rule, ranks))
        got, took = self._exchange(frames)
        want = self._want(flowparam_reference.for_deployment(self.dep),
                          frames)
        bad = sum(int((g != w).sum()) if len(g) == len(w) else len(w)
                  for g, w in zip(got, want))
        self.p.record("interleave", sum(len(w) for w in want), bad, took,
                      f"; {len(frames)} frames back to back")


def probe_checks(p) -> list:
    """The flow family's sets, the hotparam family's, and the three new."""
    c = _Checks(p)
    return (flow.probe_checks(_Side(p, p.dep.flow, FLOW))
            + hotparam.probe_checks(_Side(p, p.dep.param, PARAM))
            + [c.collide, c.chain, c.interleave])


# -- the controls of control.py -----------------------------------------------
class _Served:
    """A service with both batched entries of its own class, so that the
    device lane asks it through them, each kind through ``flow_side`` or
    ``param_side`` (the service itself unless a control stands there)."""

    flow_wrap = param_wrap = None  # a sibling family's control, or none

    def __init__(self, service):
        self._service = service
        self._flow = (self.flow_wrap or (lambda served: served))(service)
        self._param = (self.param_wrap or (lambda served: served))(service)

    def __getattr__(self, name):
        return getattr(self._service, name)

    def dispatch_batch_arrays(self, ids, acq=None, prios=None):
        return self._flow.dispatch_batch_arrays(ids, acq, prios)

    def request_batch_arrays(self, ids, acq=None, prios=None):
        return self._flow.request_batch_arrays(ids, acq, prios)

    def dispatch_params_batch(self, flow_ids, acquires, hashes):
        return self._param.dispatch_params_batch(flow_ids, acquires, hashes)

    def request_params_batch(self, flow_ids, acquires, hashes):
        return self._param.request_params_batch(flow_ids, acquires, hashes)


class FlowOverAdmit(_Served):
    """The flow family's control: the first BLOCKED verdict of every flow
    dispatch comes back OK."""

    flow_wrap = flow.OverAdmit


class ParamOverAdmit(_Served):
    """The hotparam family's control: a rule's first BLOCKED verdict after
    a quiet second comes back OK."""

    param_wrap = hotparam.OverAdmit


class CrossTalk(_Served):
    """The guarantee only this deployment has, broken: every param request
    is charged to the flow of the same number as well. No traffic rule
    shares a number across the kinds, so only ``collide`` can see it (and
    ``interleave``, whose pair shares one too)."""

    def dispatch_params_batch(self, flow_ids, acquires, hashes):
        self._service.request_batch_arrays(
            np.array(flow_ids, np.int64), np.array(acquires, np.int32))
        return self._service.dispatch_params_batch(flow_ids, acquires,
                                                   hashes)

    def request_params_batch(self, flow_ids, acquires, hashes):
        return self.dispatch_params_batch(flow_ids, acquires, hashes)()


CONTROLS = {"over_admit": FlowOverAdmit, "param_over_admit": ParamOverAdmit,
            "cross_talk": CrossTalk}
