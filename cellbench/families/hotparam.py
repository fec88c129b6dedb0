"""The hot-parameter family: ``ParamFlowRule`` deployments asked through
BATCH_PARAM_FLOW frames (codec rev 8, type 27) on the native door's data
plane. Grown from the fixture ``tests/extra/families/paramflow.py``, which
stays as it is (single PARAM_FLOW frames through the control lane).

Layout. Traffic rules have ids ``0 .. n_rules-1``. A rule limits every value
of its parameter to ``count`` tokens per window (``param.n_buckets`` buckets
of ``param.bucket_ms``); the ``hot_values`` most popular values of every
rule carry the item threshold ``hot_count`` instead (``ParamFlowItem``). A
value is known by its rank ``0 .. values_per_rule-1`` inside its rule and
travels as a 64-bit hash of (rule, rank): only hashes cross the wire. The
probe's rules have ids from ``PROBE_BASE`` up, ``PROBE_RULES_PER_SET`` to a
set, and no traffic touches them.

A row is one request: ``(rule id, acquire, value hashes[values_per_request])``.
Mix parameters of this family:

    rules       {"popularity": "zipf"|"uniform", "theta": t}: a request's rule
    values      {"dist": "zipf"|"uniform", "theta": t}: its values by rank
    values_per_request   how many values a request carries (they differ)
    acquire     tokens asked of every value

Wire. BATCH_PARAM_FLOW request ``n:u16 k:u8`` then ``n`` rows of
``flow_id:i64 count:i32 prio:u8`` and ``k`` hashes ``i64``; the response is
BATCH_FLOW's rows under type 27. Single PARAM_FLOW (type 2, the reference's
client): ``flow_id:i64 count:i32 prio:u8 n:u8`` then ``n`` hashes, response
as FLOW's with type 2 (kept for one-row mixes and the tests).

The ledger. The universe is ``n_rules x values_per_rule`` keys (2 x 10^8 in
``hot-param-1k``), which no table holds: the ledger meters the ``metered_hot``
most popular values of every rule and a seeded sample of ``metered_cold``
colder ones, holds each to its threshold per window, and counts the rest as
decided only.

The probe's checks, against ``hotparam_reference.py`` (limit 0 mismatches
unless said):

    count   one value asked ``count + 3`` times: ``count`` pass, 3 BLOCKED
    item    a value with an item threshold passes up to it, beside a value
            of the same rule that stops at the rule's count
    pair    one value exhausted, then a two-value request with it and a
            fresh one (a ``k = 2`` frame): BLOCKED, and the fresh value
            stays counted, so it passes ``count - 1`` times more
    slide   a value exhausted; one window and a bit later it passes again
    order   one frame of the cell's size with 40 rows on one value among
            rows on values asked once: the first ``count`` in frame order
            pass
    crowd   the guard on the sketch's geometry and hashing: on one rule,
            ``crowd_values`` distinct values once each with acquire
            ``count``, then ``crowd_fresh`` fresh values with acquire 1,
            inside one window. The reference passes every row; the system
            may differ in at most ``crowd_limit`` rows, each of them a
            BLOCKED where the reference passes (the count-min sketch
            over-estimates, never under): the reverse has the limit 0.
"""

from __future__ import annotations

import struct
import time

import numpy as np

from cellbench import traffic, wire
from cellbench.deploy import BLOCKED, DECIDED, NO_RULE, OK
from cellbench.families import hotparam_reference

PARAM_FLOW = 2
BATCH_PARAM_FLOW = 27
PROBE_BASE = 1_000_000
PROBE_RULES_PER_SET = 6  # count, item, pair, slide, order, crowd
# the wire holds (65535 - 5 - 3) / (13 + 8 k) requests a frame; probe and
# mixes send at most two values a request
MAX_ROWS_PER_FRAME = (65535 - 5 - 3) // (13 + 8 * 2)

SINGLE_REPLIES = ((PARAM_FLOW,), wire.SINGLE_RSP)
BATCH_REPLIES = ((BATCH_PARAM_FLOW,), wire.RSP_ROW)
_BATCH_HEAD = struct.Struct(">HibHB")


def value_hash(rule, rank) -> np.ndarray:
    """The stable 64-bit hash a client would send for value ``rank`` of
    ``rule`` (splitmix64 of the pair; any fixed mapping would do)."""
    x = (np.asarray(rule, np.uint64) * np.uint64(1_000_003)
         + np.asarray(rank, np.uint64) + np.uint64(0x9E3779B97F4A7C15))
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return x.astype(np.int64)


def encode_batch(xid: int, rule_ids, acquires, hashes) -> bytes:
    """One BATCH_PARAM_FLOW frame: ``len(rule_ids)`` requests of
    ``hashes.shape[1]`` values each."""
    n, nv = hashes.shape
    if not 1 <= nv <= 255:
        raise ValueError(f"{nv} values a request; the wire takes 1..255")
    rows = np.empty(n, np.dtype([
        ("flow_id", ">i8"), ("count", ">i4"), ("prio", "u1"),
        ("hashes", ">i8", (nv,))]))
    if 5 + 3 + rows.nbytes > 65535:
        raise ValueError(f"{n} requests of {nv} values pass the wire's frame")
    rows["flow_id"] = rule_ids
    rows["count"] = acquires
    rows["prio"] = 0
    rows["hashes"] = hashes
    return _BATCH_HEAD.pack(5 + 3 + rows.nbytes, xid, BATCH_PARAM_FLOW, n,
                            nv) + rows.tobytes()


def encode_singles(first_xid: int, rule_ids, acquires, hashes) -> np.ndarray:
    """``len(rule_ids)`` single PARAM_FLOW frames of ``hashes.shape[1]``
    values each, with consecutive xids, as one packed array."""
    n, nv = hashes.shape
    arr = np.empty(n, np.dtype([
        ("len", ">u2"), ("xid", ">i4"), ("type", "i1"), ("flow_id", ">i8"),
        ("count", ">i4"), ("prio", "u1"), ("n", "u1"),
        ("hashes", ">i8", (nv,))]))
    arr["len"] = 5 + 13 + 1 + 8 * nv
    arr["xid"] = first_xid + np.arange(n)
    arr["type"] = PARAM_FLOW
    arr["flow_id"] = rule_ids
    arr["count"] = acquires
    arr["prio"] = 0
    arr["n"] = nv
    arr["hashes"] = hashes
    return arr


class Deployment:
    def __init__(self, spec: dict):
        self.spec = spec
        self.name = spec["name"]
        r = spec["rules"]
        self.n_rules = int(r["n_rules"])
        self.values_per_rule = int(r["values_per_rule"])
        self.count = float(r["count"])
        self.hot_values = int(r["hot_values"])
        self.hot_count = float(r["hot_count"])
        self.probe_sets = int(r["probe_sets"])
        self.namespaces = int(r.get("namespaces", 1))
        self.crowd = {k: int(r[k]) for k in (
            "crowd_values", "crowd_fresh", "crowd_limit")}
        p = spec["param"]
        self.bucket_ms = int(p["bucket_ms"])
        self.window_ms = self.bucket_ms * int(p["n_buckets"])
        # the metered ranks of every rule: the most popular, and a seeded
        # sample of colder ones (the same in every process: the seed is the
        # file's)
        hot, cold = int(r["metered_hot"]), int(r["metered_cold"])
        hot = min(hot, self.values_per_rule)
        cold = min(cold, self.values_per_rule - hot)
        rng = np.random.default_rng(int(r["metered_seed"]))
        self.metered_ranks = np.concatenate([
            np.broadcast_to(np.arange(hot), (self.n_rules, hot)),
            hot + np.sort(rng.integers(
                0, self.values_per_rule - hot, (self.n_rules, cold)), axis=1),
        ], axis=1)
        h = value_hash(np.arange(self.n_rules)[:, None],
                       self.metered_ranks).reshape(-1)
        # a cold rank drawn twice is two ledger rows; tokens go to the first
        self._order = np.argsort(h, kind="stable")
        self._sorted = h[self._order]

    def rules(self):
        """Every rule: ``(rule id, count, ((value hash, threshold), ...))``."""
        for r in range(self.n_rules):
            yield r, self.count, tuple(
                (int(value_hash(r, v)), self.hot_count)
                for v in range(self.hot_values))
        for k in range(self.probe_sets * PROBE_RULES_PER_SET):
            r = PROBE_BASE + k
            # of a probe set's rules the second gives its value 0 an item
            items = (((int(value_hash(r, 0)), self.hot_count),)
                     if k % PROBE_RULES_PER_SET == 1 else ())
            yield r, self.count, items

    def probe_set(self, k: int) -> list:
        return [PROBE_BASE + k * PROBE_RULES_PER_SET + i
                for i in range(PROBE_RULES_PER_SET)]

    # -- the ledger's view of a row ------------------------------------------
    def ledger_counts(self) -> np.ndarray:
        """One key per metered (traffic rule, value): its threshold."""
        c = np.where(self.metered_ranks < self.hot_values, self.hot_count,
                     self.count)
        return c.reshape(-1).astype(np.float64)

    def ledger_view(self, cols, st, remaining):
        """Every verdict of the param lane is a decision; NO_RULE can never
        be, the rules are all loaded. A request that passed admitted its
        tokens to each of its values; those of a metered value are summed
        under its key, the others are counted as decided only. (A brownout
        pass of the overload ladder cannot be told from a pass of the
        sketch: a param verdict carries no remaining count. The ladder
        answers OVERLOAD beside it, which fails rows.)"""
        _rule_ids, acq, hashes = cols
        ok = st == OK
        h = hashes[ok].reshape(-1)
        at = np.minimum(np.searchsorted(self._sorted, h),
                        len(self._sorted) - 1)
        metered = self._sorted[at] == h
        tokens = np.repeat(acq[ok], hashes.shape[-1])[metered]
        return (DECIDED[st], np.zeros(len(st), bool),
                int((st == NO_RULE).sum()), self._order[at[metered]], tokens)

    def window_checks(self, client: dict) -> list:
        return [("requests answered NO_RULE", client["never_rows"], 0)]


# -- the generator's side: drawing rows ---------------------------------------
class Mix:
    """Draws requests of one traffic mix over one deployment. Every request
    draws its own rule, so a frame holds many rules and ``frame_tenants``
    only numbers the frames."""

    def __init__(self, tr: dict, deployment, seed: int, salt: int):
        self.d = deployment
        self.rng = np.random.default_rng([int(seed), int(salt)])
        self.frame_rows = traffic.frame_rows(tr)
        rp = tr["rules"]
        self.rule_cdf = np.cumsum(traffic.pmf(
            rp["popularity"], deployment.n_rules, rp.get("theta", 0.0)))
        vp = tr["values"]
        self.value_cdf = np.cumsum(traffic.pmf(
            vp["dist"], deployment.values_per_rule, vp.get("theta", 0.0)))
        self.n_values = int(tr["values_per_request"])
        self.acquire = int(tr["acquire"])

    def frame_tenants(self, n_frames: int) -> np.ndarray:
        return np.arange(n_frames, dtype=np.int64)

    def _draw(self, cdf, shape) -> np.ndarray:
        return np.minimum(np.searchsorted(cdf, self.rng.random(shape)),
                          len(cdf) - 1)

    def rows(self, frames: np.ndarray):
        """``(rule ids [n, rows], acquires [n, rows], hashes [n, rows,
        values])`` for ``len(frames)`` frames."""
        shape = (len(frames), self.frame_rows)
        per = self.d.values_per_rule
        rules = self._draw(self.rule_cdf, shape).astype(np.int64)
        rank = self._draw(self.value_cdf, shape + (self.n_values,))
        for j in range(1, self.n_values):  # a request's values all differ
            step = self.rng.integers(1, per, size=shape)
            clash = (rank[..., :j] == rank[..., j:j + 1]).any(axis=-1)
            while clash.any():
                rank[..., j] = np.where(clash, (rank[..., j] + step) % per,
                                        rank[..., j])
                clash = (rank[..., :j] == rank[..., j:j + 1]).any(axis=-1)
        return (rules, np.full(shape, self.acquire, np.int32),
                value_hash(rules[..., None], rank))

    def frames(self, n_frames: int):
        return self.rows(self.frame_tenants(n_frames))


for_deployment = hotparam_reference.for_deployment


# -- the program's side -------------------------------------------------------
def service_args(dep) -> dict:
    """The sketch's geometry. A program from before PR 27 has no batched
    param lane (no type 27 on its doors): said here, before anything is
    built, so that such a tree fails at once and cleanly."""
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine.param import ParamConfig

    if not hasattr(DefaultTokenService, "dispatch_params_batch"):
        raise SystemExit(
            "this program has no BATCH_PARAM_FLOW lane (DefaultTokenService."
            "dispatch_params_batch, codec rev 8): the hotparam family "
            "cannot run on it")
    return {"param_config": ParamConfig(**dep.spec["param"])}


def load_rules(service, dep) -> int:
    from sentinel_tpu.cluster.token_service import ClusterParamFlowRule

    rules = [ClusterParamFlowRule(r, count, item_thresholds=items or None,
                                  namespace=f"gw{r % dep.namespaces}")
             for r, count, items in dep.rules()]
    service.load_param_rules(rules)
    n_rules = len(service.current_param_rules())
    if n_rules != len(rules):
        raise RuntimeError(f"{n_rules} param rules loaded, {len(rules)} in "
                           f"the file")
    return n_rules


def drive_before_window(built, tr: dict, dep, seed: int, compiles: list,
                        say) -> list:
    """In process, before the window: one batch of the mix's own rows at
    every serve bucket (``warmup()`` compiled them on a throwaway sketch;
    this runs each once on the live one). Says which kernel ``impl`` chose.
    Nothing fuses on the param lane."""
    service = built.service
    cfg = service.param_config
    kernel, reason = service.param_impl()
    say(f"param path: impl {cfg.impl!r} resolved to {kernel!r} ({reason}); "
        f"sketch {cfg.sketch} {cfg.max_param_rules} x {cfg.n_buckets} x "
        f"{cfg.depth} x {cfg.width}")
    mix = Mix(tr, dep, seed, 991)
    nv = mix.n_values
    for bucket in dep.spec["serve_buckets"]:
        n = max(1, int(bucket) // nv)
        rules, acq, hashes = mix.frames(-(-n // mix.frame_rows))
        n0 = len(compiles)
        service.request_params_batch(
            rules.reshape(-1)[:n], acq.reshape(-1)[:n],
            hashes.reshape(-1, nv)[:n])
        say(f"warm-up: {n} requests of {nv} value(s) in process (bucket "
            f"{bucket}), {len(compiles) - n0} compiles")
    return []


def progress(built):
    """What the stall watch expects to keep rising: dispatches whose
    verdicts the reply lanes have materialized."""
    from sentinel_tpu.metrics.server import server_metrics

    done = server_metrics().decide_ms
    return lambda: done.count


# -- the probe's sets ---------------------------------------------------------
class _Checks:
    def __init__(self, p):
        self.p, self.dep = p, p.dep
        self.rules = p.dep.probe_set(p.probe_set)
        self.limit = int(p.dep.count)
        self.ref = for_deployment(p.dep)
        self.now = 10_000  # the reference's clock, ms

    def _ask(self, requests, acquire: int = 1) -> tuple:
        """``requests``: ``[(rule, [value ranks])]`` in order. Sends them
        (runs of one value count are one exchange) and returns ``(status,
        wanted, seconds)``."""
        got, want, took, i = [], [], 0.0, 0
        while i < len(requests):
            j = i
            while (j < len(requests)
                   and len(requests[j][1]) == len(requests[i][1])):
                j += 1
            rules = np.array([r for r, _v in requests[i:j]], np.int64)
            hashes = np.stack([value_hash(r, np.asarray(v))
                               for r, v in requests[i:j]])
            acq = np.full(len(rules), acquire, np.int32)
            status, _wait, t = self.p.send(rules, acq, hashes)
            got.append(status)
            want.extend(self.ref.decide_all(self.now, rules, acq, hashes))
            took += t
            i = j
        return np.concatenate(got), np.asarray(want, np.int8), took

    def _record(self, name: str, requests) -> None:
        got, want, took = self._ask(requests)
        self.p.record(name, len(requests), int((got != want).sum()), took)

    def count(self) -> None:
        self._record("count", [(self.rules[0], [3])] * (self.limit + 3))

    def item(self) -> None:
        hot = int(self.dep.hot_count)
        reqs = ([(self.rules[1], [0])] * (hot + 2)
                + [(self.rules[1], [5])] * (self.limit + 2))
        self._record("item", [reqs[k] for k in
                              self.p.rng.permutation(len(reqs))])

    def pair(self) -> None:
        r = self.rules[2]
        self._record("pair", [(r, [1])] * self.limit + [(r, [1, 2])]
                     + [(r, [2])] * (self.limit + 1))

    def slide(self) -> None:
        r = self.rules[3]
        reqs = [(r, [4])] * (self.limit + 1)
        got, want, took = self._ask(reqs)
        wait_ms = self.dep.window_ms + 100
        time.sleep(wait_ms / 1000.0)
        self.now += wait_ms + int(took * 1000) + 1
        got2, want2, took2 = self._ask(reqs)
        self.p.record("slide", 2 * len(reqs), int((got != want).sum())
                      + int((got2 != want2).sum()), took + took2)

    def order(self) -> None:
        """One frame of the cell's own size: 40 rows on one value at seeded
        places among rows on values asked once each."""
        r = self.rules[4]
        n = max(self.p.frame_rows, 64)
        ranks = 100 + np.arange(n)
        ranks[self.p.rng.choice(n, 40, replace=False)] = 7
        self._record("order", [(r, [int(v)]) for v in ranks])

    def crowd(self) -> None:
        c = self.dep.crowd
        r = self.rules[5]
        first = [(r, [v]) for v in range(c["crowd_values"])]
        fresh = [(r, [c["crowd_values"] + v])
                 for v in range(c["crowd_fresh"])]
        got, want, took = self._ask(first, acquire=self.limit)
        got2, want2, took2 = self._ask(fresh, acquire=1)
        got, want = np.concatenate([got, got2]), np.concatenate([want, want2])
        early = int(((got == BLOCKED) & (want == OK)).sum())
        other = int((got != want).sum()) - early
        # limits of their own, so not ``record``: the rows the sketch
        # refused early, and every other difference
        for name, bad, limit in (("crowd", early, c["crowd_limit"]),
                                 ("crowd_other", other, 0)):
            self.p.checks.append({
                "check": name, "rows": len(got), "mismatches": bad,
                "limit": limit, "seconds": took + took2, "ok": bad <= limit})
            self.p.say(f"probe {name}: {len(got)} rows, {bad} mismatches "
                       f"(limit {limit}), {(took + took2) * 1e3:.1f} ms")


def probe_checks(p) -> list:
    c = _Checks(p)
    return [c.count, c.item, c.pair, c.slide, c.order, c.crowd]


# -- the control --------------------------------------------------------------
class OverAdmit:
    """The service with one answer altered where it is produced, on the
    batched entry: a rule's first BLOCKED verdict after a second without one
    comes back OK, an exhausted value let through."""

    def __init__(self, service):
        self._service = service
        self._last_blocked = {}  # rule id -> monotonic seconds

    def __getattr__(self, name):
        return getattr(self._service, name)

    def dispatch_params_batch(self, flow_ids, acquires, hashes):
        rules = np.array(flow_ids, np.int64)  # a copy: the door reuses its
        mat = self._service.dispatch_params_batch(flow_ids, acquires, hashes)

        def altered():
            status, remaining, wait = mat()
            blocked = np.flatnonzero(status == BLOCKED)
            if blocked.size:
                now = time.monotonic()
                status = status.copy()
                first = np.unique(rules[blocked], return_index=True)
                for rule, at in zip(*first):
                    if now - self._last_blocked.get(int(rule), -10.0) > 1.0:
                        status[blocked[at]] = OK
                    self._last_blocked[int(rule)] = now
            return status, remaining, wait
        return altered

    def request_params_batch(self, flow_ids, acquires, hashes):
        return self.dispatch_params_batch(flow_ids, acquires, hashes)()


CONTROLS = {"over_admit": OverAdmit}
