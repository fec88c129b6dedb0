"""The PARAM_FLOW family, as a fixture: hot-parameter rules
(``ClusterParamFlowRule``) asked with single PARAM_FLOW frames, on the
program as it is (the native door hands them to the control lane, which
answers one at a time). It proves that a second family needs no edit of a
file that is there; it is not a benchmark configuration. The next
``model_config`` PR grows it into ``cellbench/families/`` as new files.

Layout. Traffic rules have ids ``0 .. n_rules-1``. A rule limits every value
of its parameter to ``count`` tokens per window (1 s: two buckets of 500 ms);
the ``hot_values`` most popular values of every rule carry the item
threshold ``hot_count`` instead. A value is known by its rank ``0 ..
values_per_rule-1`` inside its rule, and travels as a 64-bit hash of (rule,
rank). The probe's rules have ids from ``PROBE_BASE`` up, four to a set, and
no traffic touches them.

A row is one request: ``(rule id, acquire, value hashes[values_per_request])``.
Mix parameters of this family:

    rules       {"popularity": "zipf"|"uniform", "theta": t}: a request's rule
    values      {"dist": "zipf"|"uniform", "theta": t}: its values by rank
    values_per_request   how many values a request carries (they differ)
    acquire     tokens asked of every value

Wire (the reference's client): request ``flow_id:i64 count:i32 prio:u8 n:u8``
then ``n`` value hashes ``i64``; response as FLOW's, with type 2.

The probe's checks (every comparison has the limit 0 mismatches):

    count   one value asked ``count + 3`` times: ``count`` pass, 3 BLOCKED
    item    a value with an item threshold passes up to it, beside a value
            of the same rule that stops at the rule's count
    pair    one value exhausted, then a two-value request with it and a
            fresh one: BLOCKED, and the fresh value stays counted, so it
            passes ``count - 1`` times more and no further
    slide   a value exhausted; one window and a bit later it passes again
"""

from __future__ import annotations

import time

import numpy as np

from cellbench import traffic, wire
from cellbench.deploy import BLOCKED, DECIDED, NO_RULE, OK

PARAM_FLOW = 2
PROBE_BASE = 1_000_000
PROBE_RULES_PER_SET = 4
MAX_ROWS_PER_FRAME = 1  # PARAM_FLOW has no batch frame on the wire

SINGLE_REPLIES = ((PARAM_FLOW,), wire.SINGLE_RSP)
BATCH_REPLIES = ((), wire.RSP_ROW)


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


def encode_singles(first_xid: int, rule_ids, acquires, hashes) -> np.ndarray:
    """``len(rule_ids)`` PARAM_FLOW request frames of ``hashes.shape[1]``
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


def encode_batch(xid: int, *cols) -> bytes:
    raise NotImplementedError("PARAM_FLOW has no batch frame on the wire")


class _RankOf:
    """``hash -> rank`` of every traffic value, by array."""

    def __init__(self, dep):
        h = value_hash(np.arange(dep.n_rules)[:, None],
                       np.arange(dep.values_per_rule)[None, :]).reshape(-1)
        self.order = np.argsort(h)
        self.sorted = h[self.order]
        self.per = dep.values_per_rule

    def __getitem__(self, hashes):
        at = np.searchsorted(self.sorted, hashes)
        return self.order[at] % self.per


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
        p = spec["param"]
        self.bucket_ms = int(p["bucket_ms"])
        self.window_ms = self.bucket_ms * int(p["n_buckets"])
        self._rank_of = _RankOf(self)

    def rules(self):
        """Every rule: ``(rule id, count, ((value hash, threshold), ...))``."""
        for r in range(self.n_rules):
            yield r, self.count, tuple(
                (int(value_hash(r, v)), self.hot_count)
                for v in range(self.hot_values))
        for k in range(self.probe_sets * PROBE_RULES_PER_SET):
            r = PROBE_BASE + k
            # of a probe set's four rules the second gives its value 0 an item
            items = (((int(value_hash(r, 0)), self.hot_count),)
                     if k % PROBE_RULES_PER_SET == 1 else ())
            yield r, self.count, items

    def probe_set(self, k: int) -> list:
        return [PROBE_BASE + k * PROBE_RULES_PER_SET + i
                for i in range(PROBE_RULES_PER_SET)]

    # -- the ledger's view of a row ------------------------------------------
    def ledger_counts(self) -> np.ndarray:
        """One key per (traffic rule, value rank): its threshold."""
        c = np.full((self.n_rules, self.values_per_rule), self.count)
        c[:, :self.hot_values] = self.hot_count
        return c.reshape(-1)

    def ledger_view(self, cols, st, remaining):
        """Every verdict of the control lane is a decision and none is a
        brownout pass; NO_RULE can never be, the rules are all loaded. A
        request that passed admitted its tokens to each of its values."""
        rule_ids, acq, hashes = cols
        ok = st == OK
        # the rank of a value is not on the wire: find it by its hash
        ranks = self._rank_of[hashes[ok]]
        keys = (rule_ids[ok][:, None] * self.values_per_rule + ranks)
        tokens = np.repeat(acq[ok], hashes.shape[-1])
        return (DECIDED[st], np.zeros(len(st), bool),
                int((st == NO_RULE).sum()), keys.reshape(-1), tokens)

    def window_checks(self, client: dict) -> list:
        return [("requests answered NO_RULE",
                 client["never_rows"], 0)]


# -- the generator's side: drawing rows ---------------------------------------
class Mix:
    """Draws requests of one traffic mix over one deployment. A frame is one
    request, so ``frame_tenants`` says which rule each request asks."""

    def __init__(self, tr: dict, deployment, seed: int, salt: int):
        if tr["msg"] != "single":
            raise ValueError("PARAM_FLOW has no batch frame on the wire")
        self.d = deployment
        self.rng = np.random.default_rng([int(seed), int(salt)])
        self.frame_rows = 1
        rp = tr["rules"]
        self.rule_p = traffic.pmf(rp["popularity"], deployment.n_rules,
                                  rp.get("theta", 0.0))
        vp = tr["values"]
        self.value_cdf = np.cumsum(traffic.pmf(
            vp["dist"], deployment.values_per_rule, vp.get("theta", 0.0)))
        self.n_values = int(tr["values_per_request"])
        self.acquire = int(tr["acquire"])

    def frame_tenants(self, n_frames: int) -> np.ndarray:
        who = np.repeat(np.arange(self.d.n_rules, dtype=np.int64),
                        traffic.apportion(self.rule_p, n_frames))
        self.rng.shuffle(who)
        return who

    def rows(self, rules: np.ndarray):
        """``(rule ids [n, 1], acquires [n, 1], hashes [n, 1, values])``."""
        n, per = len(rules), self.d.values_per_rule
        rank = np.minimum(np.searchsorted(
            self.value_cdf, self.rng.random((n, self.n_values))), per - 1)
        for j in range(1, self.n_values):  # a request's values all differ
            step = self.rng.integers(1, per, size=n)
            clash = (rank[:, :j] == rank[:, j:j + 1]).any(axis=1)
            while clash.any():
                rank[clash, j] = (rank[clash, j] + step[clash]) % per
                clash = (rank[:, :j] == rank[:, j:j + 1]).any(axis=1)
        hashes = value_hash(rules[:, None], rank)
        return (rules[:, None], np.full((n, 1), self.acquire, np.int32),
                hashes[:, None, :])

    def frames(self, n_frames: int):
        return self.rows(self.frame_tenants(n_frames))


# -- the plain reference ------------------------------------------------------
class Reference:
    """A scalar reference of the per-value window: exact counts per
    ``(rule, value)`` in a sliding window of ``n_buckets`` x ``bucket_ms``.
    A request passes only if every value has headroom under its threshold
    (the item's, else the rule's count); the values that had headroom stay
    counted when another value blocks the request, as
    ``DefaultTokenService.request_params_token`` documents. Imports nothing
    of the program."""

    def __init__(self, rules, bucket_ms: int, n_buckets: int):
        """``rules``: ``{rule id: (count, {value hash: threshold})}``."""
        self.rules = dict(rules)
        self.bucket_ms, self.n_buckets = bucket_ms, n_buckets
        self.windows = {}  # (rule, hash) -> {bucket start: tokens}

    def _total(self, key, t_ms: int) -> float:
        w = self.windows.setdefault(key, {})
        oldest = (t_ms - t_ms % self.bucket_ms
                  - (self.n_buckets - 1) * self.bucket_ms)
        for s in [s for s in w if s < oldest]:
            del w[s]
        return sum(w.values())

    def decide(self, t_ms: int, rule: int, acquire: int, hashes) -> int:
        entry = self.rules.get(rule)
        if entry is None:
            return NO_RULE
        count, items = entry
        every = True
        for h in hashes:
            key = (rule, int(h))
            if self._total(key, t_ms) + acquire <= items.get(int(h), count):
                w = self.windows[key]
                start = t_ms - t_ms % self.bucket_ms
                w[start] = w.get(start, 0.0) + acquire
            else:
                every = False
        return OK if every else BLOCKED


def for_deployment(dep) -> Reference:
    return Reference({r: (count, dict(items))
                      for r, count, items in dep.rules()},
                     dep.bucket_ms, dep.window_ms // dep.bucket_ms)


# -- the program's side -------------------------------------------------------
def service_args(dep) -> dict:
    from sentinel_tpu.engine.param import ParamConfig

    return {"param_config": ParamConfig(**dep.spec["param"])}


def load_rules(service, dep) -> int:
    from sentinel_tpu.cluster.token_service import ClusterParamFlowRule

    rules = [ClusterParamFlowRule(r, count, item_thresholds=items or None)
             for r, count, items in dep.rules()]
    service.load_param_rules(rules)
    n_rules = len(service.current_param_rules())
    if n_rules != len(rules):
        raise RuntimeError(f"{n_rules} param rules loaded, {len(rules)} in "
                           f"the file")
    return n_rules


def drive_before_window(built, tr: dict, dep, seed: int, compiles: list,
                        say) -> list:
    """Nothing to drive: ``warmup()`` compiles the one padded shape a
    request of up to eight values uses. Says which kernel ``impl`` chose."""
    from sentinel_tpu.engine.param import explain_param_impl

    cfg = built.service.param_config
    kernel, reason = explain_param_impl(cfg.impl, cfg.sketch)
    say(f"param path: impl {cfg.impl!r} resolved to {kernel!r} ({reason}); "
        f"sketch {cfg.sketch} {cfg.max_param_rules} x {cfg.n_buckets} x "
        f"{cfg.depth} x {cfg.width}")
    return []


def progress(built):
    """The program counts nothing on the control lane: the door's bytes
    written rise with every reply."""
    return lambda: built.server.stats()["bytes_out"]


# -- the probe's sets ---------------------------------------------------------
class _Checks:
    def __init__(self, p):
        self.p, self.dep = p, p.dep
        self.rules = p.dep.probe_set(p.probe_set)
        self.limit = int(p.dep.count)
        self.ref = for_deployment(p.dep)
        self.now = 10_000  # the reference's clock, ms

    def _ask(self, requests) -> tuple:
        """``requests``: ``[(rule, [value ranks])]`` in order. Sends them
        (runs of one value count are one exchange) and returns
        ``(mismatches, seconds)`` against the reference."""
        bad, took, i = 0, 0.0, 0
        while i < len(requests):
            j = i
            while (j < len(requests)
                   and len(requests[j][1]) == len(requests[i][1])):
                j += 1
            rules = np.array([r for r, _v in requests[i:j]], np.int64)
            hashes = np.stack([value_hash(r, np.asarray(v))
                               for r, v in requests[i:j]])
            status, _wait, t = self.p.send(
                rules, np.ones(len(rules), np.int32), hashes)
            want = [self.ref.decide(self.now, int(r), 1, h)
                    for r, h in zip(rules, hashes)]
            bad += int((status != np.asarray(want, np.int8)).sum())
            took += t
            i = j
        return bad, took

    def count(self) -> None:
        n = self.limit + 3
        bad, took = self._ask([(self.rules[0], [3])] * n)
        self.p.record("count", n, bad, took)

    def item(self) -> None:
        hot = int(self.dep.hot_count)
        reqs = ([(self.rules[1], [0])] * (hot + 2)
                + [(self.rules[1], [5])] * (self.limit + 2))
        order = self.p.rng.permutation(len(reqs))
        bad, took = self._ask([reqs[k] for k in order])
        self.p.record("item", len(reqs), bad, took)

    def pair(self) -> None:
        r = self.rules[2]
        reqs = ([(r, [1])] * self.limit + [(r, [1, 2])]
                + [(r, [2])] * (self.limit + 1))
        bad, took = self._ask(reqs)
        self.p.record("pair", len(reqs), bad, took)

    def slide(self) -> None:
        r = self.rules[3]
        bad, took = self._ask([(r, [4])] * (self.limit + 1))
        wait_ms = self.dep.window_ms + 100
        time.sleep(wait_ms / 1000.0)
        self.now += wait_ms + int(took * 1000) + 1
        bad2, took2 = self._ask([(r, [4])] * (self.limit + 1))
        self.p.record("slide", 2 * (self.limit + 1), bad + bad2, took + took2)


def probe_checks(p) -> list:
    c = _Checks(p)
    return [c.count, c.item, c.pair, c.slide]


# -- the control --------------------------------------------------------------
class OverAdmit:
    """The service with one answer altered where it is produced: a rule's
    first BLOCKED verdict after a second without one comes back OK, an
    exhausted value let through."""

    def __init__(self, service):
        self._service = service
        self._last_blocked = {}  # rule id -> monotonic seconds

    def __getattr__(self, name):
        return getattr(self._service, name)

    def request_params_token(self, flow_id, acquire, param_hashes):
        r = self._service.request_params_token(flow_id, acquire, param_hashes)
        if int(r.status) == BLOCKED:
            now = time.monotonic()
            last = self._last_blocked.get(flow_id, -10.0)
            self._last_blocked[flow_id] = now
            if now - last > 1.0:
                return type(r)(type(r.status)(OK))
        return r


CONTROLS = {"over_admit": OverAdmit}
