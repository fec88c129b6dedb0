"""The flow family's plain reference: what a token server with these rules
must answer.

A straightforward scalar implementation of the semantics the deployment's
file states, one request at a time, in plain Python floats and ints. It
imports nothing of the program (not ``sentinel_tpu.local`` either) and takes
nothing the program made. Sources: the reference's ``ClusterFlowChecker``
(sliding window of ``n_buckets`` x ``bucket_ms``; a request of ``a`` tokens
passes while ``passed + a <= count * interval_s``), ``GlobalRequestLimiter``
(requests per namespace per interval, counted per request, refused with
TOO_MANY_REQUEST before any flow is looked at) and
``RateLimiterController`` (a request's cost is ``round(1000 * a / count)``
ms; an idle flow's first request passes now, later ones are told to wait
``latest - now`` ms, and are BLOCKED when that exceeds the queueing limit).

``lower_precision=True`` is the control: the same reference with every
running total rounded to bfloat16's 8 significant bits, the way a
single-pass matrix unit would accumulate it. Counts above 256 then go wrong,
which the comparison has to see.
"""

from __future__ import annotations

import math

from cellbench.deploy import (BLOCKED, DEFAULT, NO_RULE, OK, RATE_LIMITER,
                              SHOULD_WAIT, TOO_MANY)


def _bf16(x: float) -> float:
    """``x`` rounded to 8 significant bits (round to nearest even)."""
    if x == 0:
        return 0.0
    m, e = math.frexp(x)  # x = m * 2**e, 0.5 <= |m| < 1
    return math.ldexp(round(m * 256.0) / 256.0, e)


class Window:
    """A sliding window of ``n`` buckets of ``width`` ms (``LeapArray``)."""

    def __init__(self, width: int, n: int):
        self.width, self.n = width, n
        self.buckets = {}  # bucket start -> total

    def add(self, t_ms: int, v: float) -> None:
        start = t_ms - t_ms % self.width
        self.buckets[start] = self.buckets.get(start, 0.0) + v

    def total(self, t_ms: int) -> float:
        oldest = t_ms - t_ms % self.width - (self.n - 1) * self.width
        for s in [s for s in self.buckets if s < oldest]:
            del self.buckets[s]
        return sum(self.buckets.values())


class Reference:
    def __init__(self, rules, ns_max_qps: float, bucket_ms: int,
                 n_buckets: int, max_queue_ms: int = 500,
                 lower_precision: bool = False):
        """``rules``: ``{flow_id: (count, namespace, behaviour)}``."""
        self.rules = dict(rules)
        self.ns_max_qps = float(ns_max_qps)
        self.bucket_ms, self.n_buckets = bucket_ms, n_buckets
        self.interval_s = bucket_ms * n_buckets / 1000.0
        self.max_queue_ms = max_queue_ms
        self.round = _bf16 if lower_precision else (lambda x: x)
        self.flow_win = {}
        self.ns_win = {}
        self.latest = {}  # paced flows: latest passed time, ms

    def _win(self, table: dict, key) -> Window:
        w = table.get(key)
        if w is None:
            w = table[key] = Window(self.bucket_ms, self.n_buckets)
        return w

    def decide(self, t_ms: int, flow_id: int, acquire: int):
        """``(status, wait_ms)`` of one request arriving at ``t_ms``."""
        rule = self.rules.get(flow_id)
        if rule is None:
            return NO_RULE, 0
        count, ns, behaviour = rule
        nsw = self._win(self.ns_win, ns)
        seen = self.round(nsw.total(t_ms))
        if seen + 1.0 > self.round(self.ns_max_qps * self.interval_s):
            return TOO_MANY, 0
        nsw.add(t_ms, 1.0)
        if behaviour == DEFAULT:
            fw = self._win(self.flow_win, flow_id)
            passed = self.round(fw.total(t_ms))
            if passed + acquire <= self.round(count * self.interval_s):
                fw.add(t_ms, float(acquire))
                return OK, 0
            return BLOCKED, 0
        if behaviour == RATE_LIMITER:
            cost = round(1000.0 * acquire / count)
            latest = max(self.latest.get(flow_id, -(1 << 40)), t_ms - cost)
            due = latest + cost
            wait = due - t_ms
            if wait > self.max_queue_ms:
                return BLOCKED, 0
            self.latest[flow_id] = due
            return (OK, 0) if wait <= 0 else (SHOULD_WAIT, int(wait))
        raise ValueError(f"behaviour {behaviour} has no reference here")

    def decide_frame(self, t_ms: int, flow_ids, acquires):
        """A frame's rows share one arrival time and are decided in order."""
        out = [self.decide(t_ms, int(f), int(a))
               for f, a in zip(flow_ids, acquires)]
        return [s for s, _ in out], [w for _, w in out]


def for_deployment(dep, lower_precision: bool = False) -> Reference:
    rules = {fid: (count, ns, behaviour)
             for fid, count, ns, behaviour in dep.rules()}
    e = dep.spec["engine"]
    return Reference(rules, dep.ns_max_qps, int(e["bucket_ms"]),
                     int(e["n_buckets"]), lower_precision=lower_precision)
