"""The shaped family's plain reference: what a token server must answer when
its rules carry the four control behaviours and its rows a priority flag.

One request at a time, in arrival order, in plain Python floats (float64) and
ints. It imports nothing of the program and takes nothing the program made.
Written from the published controllers of the reference implementation:

``ClusterFlowChecker.acquireClusterToken`` / ``GlobalRequestLimiter``
    the window of ``n_buckets`` x ``bucket_ms`` and the namespace guard, as
    ``flow_reference.py`` has them: a request of ``a`` tokens passes while
    ``passed + a <= count * interval_s``.
``WarmUpController`` (``:64-170``)
    ``construct``: ``warningToken = period * count / (cold - 1)``,
    ``maxToken = warningToken + 2 * period * count / (1 + cold)``, ``slope =
    (cold - 1) / count / (maxToken - warningToken)``. ``syncToken`` once a
    second, lazily on a request: ``coolDownTokens`` (refill at ``count``/s
    below the warning line, or above it while the pass rate is under
    ``count / cold``; capped at ``maxToken``), then the last second's passes
    are taken out. ``canPass``: above the warning line the rate allowed is
    ``1 / (aboveToken * slope + 1 / count)``, at or below it ``count``. A
    flow that never ran starts full, which is cold.
``RateLimiterController`` (``:46-91``)
    a request costs ``round(1000 * a / count)`` ms; an idle flow's first
    request passes now; later ones are due ``cost`` after the latest grant,
    told to wait that long (SHOULD_WAIT), and BLOCKED once the wait would
    pass ``maxQueueingTimeMs``.
``WarmUpRateLimiterController`` (``:27``)
    the pacer, with the cost taken at the curve's current rate.
``ClusterFlowChecker`` ``:84-97`` (``canOccupy`` / ``tryOccupyNext``)
    a prioritized request over the threshold may book its tokens in the next
    bucket: it is told to wait until that bucket starts (SHOULD_WAIT) if,
    with what the coming bucket lets go of and what is already booked, the
    window still holds it, and the wait is within the occupy timeout. Booked
    tokens count as passed in the window they were booked in.

Where this departs from the Java, each time because the token server decides
a frame at a time on a millisecond clock of its own:

* **one arrival time per frame, rows decided in order.** A frame's rows share
  its instant; ``decide_frame`` takes it once.
* **ms clock.** The warm-up curve is synced when a request finds the second
  (``t - t % 1000``) later than the last sync, on the server's own clock,
  whose seconds no client can see: the probe's ``warm_slide`` says how it
  lives with that.
* **the pass rate of the sync is the sliding window's**, not a counter of the
  calendar second before: ``passed * 1000 / interval``. With a one-second
  window that is the last second's passes.
* **a paced wait is booked** like a priority borrow: the tokens of a
  SHOULD_WAIT row count as passed in the bucket the wait ends in (the window
  of a paced flow decides nothing, but the curve of a WARM_UP_RATE_LIMITER
  flow reads it). The wait of a borrow runs to the start of the next
  *bucket* (``bucket_ms - t % bucket_ms``, at most the occupy timeout), where
  the Java answers one bucket's length.
* **what the coming bucket lets go of** are the tokens *passed* in the oldest
  bucket of the window; tokens *booked* into that bucket are not given back
  early (they leave with the bucket).
* **only DEFAULT flows lend**: a prioritized row on a shaped flow is decided
  as any other.

``lower_precision=True`` is the control, as in ``flow_reference.py``: every
running total rounded to bfloat16's 8 significant bits.
"""

from __future__ import annotations

from cellbench.deploy import (BLOCKED, DEFAULT, NO_RULE, OK, RATE_LIMITER,
                              SHOULD_WAIT, TOO_MANY, WARM_UP,
                              WARM_UP_RATE_LIMITER)
from cellbench.families.flow_reference import Window, _bf16

NEVER = -(1 << 40)


class Rule:
    def __init__(self, count, namespace, behaviour=DEFAULT,
                 warm_up_period_sec=10, cold_factor=3,
                 max_queueing_time_ms=500):
        self.count, self.namespace = float(count), namespace
        self.behaviour = int(behaviour)
        self.max_queue_ms = int(max_queueing_time_ms)
        if self.behaviour in (WARM_UP, WARM_UP_RATE_LIMITER):
            # WarmUpController.construct
            cold = max(2, int(cold_factor))
            period = max(1, int(warm_up_period_sec))
            self.warning = float(int(period * self.count / (cold - 1)))
            self.max_token = float(int(
                self.warning + 2.0 * period * self.count / (1.0 + cold)))
            self.slope = (cold - 1.0) / self.count / max(
                1.0, self.max_token - self.warning)
            self.cold_count = float(int(self.count) // cold)


class _Flow:
    """What a flow's controllers keep between requests."""

    def __init__(self, bucket_ms: int, n_buckets: int):
        self.passed = Window(bucket_ms, n_buckets)
        self.booked = {}  # bucket start -> tokens booked into it
        self.latest = NEVER  # RateLimiterController.latestPassedTime
        self.stored = 0.0  # WarmUpController.storedTokens
        self.filled = NEVER  # WarmUpController.lastFilledTime


class Reference:
    def __init__(self, rules, ns_max_qps: float, bucket_ms: int,
                 n_buckets: int, occupy_timeout_ms: int = 500,
                 lower_precision: bool = False):
        """``rules``: ``{flow_id: Rule}``."""
        self.rules = dict(rules)
        self.ns_max_qps = float(ns_max_qps)
        self.bucket_ms, self.n_buckets = bucket_ms, n_buckets
        self.interval_ms = bucket_ms * n_buckets
        self.interval_s = self.interval_ms / 1000.0
        self.occupy_timeout_ms = occupy_timeout_ms
        self.round = _bf16 if lower_precision else (lambda x: x)
        self.flows = {}
        self.ns_win = {}
        # the closest a threshold or a cost came to the edge of its rounding,
        # as a share of itself: what float32 can get wrong lies under 1e-6
        self.closest = 1.0

    # -- state ---------------------------------------------------------------
    def _flow(self, flow_id) -> _Flow:
        f = self.flows.get(flow_id)
        if f is None:
            f = self.flows[flow_id] = _Flow(self.bucket_ms, self.n_buckets)
        return f

    def _bucket(self, t_ms: int) -> int:
        return t_ms - t_ms % self.bucket_ms

    def _matured(self, f: _Flow, t_ms: int) -> float:
        """Booked tokens whose bucket has begun and is still in the window."""
        for s in [s for s in f.booked if t_ms - s >= self.interval_ms]:
            del f.booked[s]
        return sum(v for s, v in f.booked.items() if s <= t_ms)

    def _waiting(self, f: _Flow, t_ms: int) -> float:
        """Booked tokens whose bucket has not begun."""
        return sum(v for s, v in f.booked.items() if s > t_ms)

    def _passed(self, f: _Flow, t_ms: int) -> float:
        return self.round(f.passed.total(t_ms) + self._matured(f, t_ms))

    def _book(self, f: _Flow, t_ms: int, wait_ms: int, tokens: int) -> None:
        """``tokens`` into the bucket ``wait_ms`` ahead: at least the next
        one, at most the last the window can hold ahead."""
        ahead = (t_ms + wait_ms - self._bucket(t_ms)) // self.bucket_ms
        ahead = min(max(ahead, 1), self.n_buckets - 1)
        s = self._bucket(t_ms) + ahead * self.bucket_ms
        f.booked[s] = f.booked.get(s, 0.0) + tokens

    def _near(self, x: float, edge: float) -> None:
        """Record how close ``x``'s fraction came to ``edge`` (0: a whole
        number, the edge of a threshold that counts whole tokens; 0.5: the
        edge of a cost's rounding). Asked only of numbers that come off the
        warm-up curve or out of a division: a count times the interval is
        whole in any precision."""
        frac = x - int(x)
        gap = abs(frac - edge) if edge else min(frac, 1.0 - frac)
        self.closest = min(self.closest, gap / max(abs(x), 1.0))

    # -- the controllers -----------------------------------------------------
    def _rate(self, rule: Rule, f: _Flow, t_ms: int, passed: float) -> float:
        """WarmUpController: sync once a second, then the rate allowed."""
        second = t_ms - t_ms % 1000
        if second > f.filled:  # syncToken
            pass_qps = passed * 1000.0 / self.interval_ms
            stored = f.stored
            if stored < rule.warning or (stored > rule.warning
                                         and pass_qps < rule.cold_count):
                # coolDownTokens; a flow that never ran fills to the brim
                stored += (second - f.filled) * rule.count / 1000.0
            stored = min(stored, rule.max_token)
            f.stored = self.round(max(stored - pass_qps, 0.0))
            f.filled = second
        if f.stored >= rule.warning:  # canPass, above the warning line
            above = f.stored - rule.warning
            return 1.0 / (above * rule.slope + 1.0 / rule.count)
        return rule.count

    def _pace(self, rule: Rule, f: _Flow, t_ms: int, acquire: int,
              rate: float):
        """RateLimiterController.canPass at ``rate`` tokens a second."""
        exact = 1000.0 * acquire / rate
        self._near(exact, 0.5)
        cost = round(exact)
        due = max(f.latest, t_ms - cost) + cost
        wait = due - t_ms
        if wait > rule.max_queue_ms:
            return BLOCKED, 0
        f.latest = due
        if wait <= 0:
            f.passed.add(t_ms, float(acquire))
            return OK, 0
        self._book(f, t_ms, wait, acquire)
        return SHOULD_WAIT, int(wait)

    def _occupy(self, f: _Flow, t_ms: int, acquire: int, passed: float,
                threshold: float):
        """tryOccupyNext: book ``acquire`` into the next bucket if the window
        that begins there still holds it."""
        wait = self.bucket_ms - t_ms % self.bucket_ms
        if wait > self.occupy_timeout_ms:
            return BLOCKED, 0
        oldest = self._bucket(t_ms) - (self.n_buckets - 1) * self.bucket_ms
        leaving = f.passed.buckets.get(oldest, 0.0)
        held = self.round(passed - leaving + self._waiting(f, t_ms))
        if held + acquire <= threshold:
            self._book(f, t_ms, wait, acquire)
            return SHOULD_WAIT, int(wait)
        return BLOCKED, 0

    # -- one request ---------------------------------------------------------
    def decide(self, t_ms: int, flow_id: int, acquire: int,
               prioritized: bool = False):
        """``(status, wait_ms)`` of one request arriving at ``t_ms``."""
        rule = self.rules.get(flow_id)
        if rule is None:
            return NO_RULE, 0
        nsw = self.ns_win.get(rule.namespace)
        if nsw is None:
            nsw = self.ns_win[rule.namespace] = Window(self.bucket_ms,
                                                       self.n_buckets)
        seen = self.round(nsw.total(t_ms))
        if seen + 1.0 > self.round(self.ns_max_qps * self.interval_s):
            return TOO_MANY, 0
        nsw.add(t_ms, 1.0)
        f = self._flow(flow_id)
        passed = self._passed(f, t_ms)
        rate = rule.count
        if rule.behaviour in (WARM_UP, WARM_UP_RATE_LIMITER):
            rate = self._rate(rule, f, t_ms, passed)
        if rule.behaviour in (RATE_LIMITER, WARM_UP_RATE_LIMITER):
            return self._pace(rule, f, t_ms, acquire, rate)
        threshold = self.round(rate * self.interval_s)
        if rule.behaviour == WARM_UP:
            self._near(threshold, 0.0)
        if passed + acquire <= threshold:
            f.passed.add(t_ms, float(acquire))
            return OK, 0
        if prioritized and rule.behaviour == DEFAULT:
            return self._occupy(f, t_ms, acquire, passed, threshold)
        return BLOCKED, 0

    def decide_frame(self, t_ms: int, flow_ids, acquires, prioritized=None):
        """A frame's rows share one arrival time and are decided in order."""
        if prioritized is None:
            prioritized = [False] * len(flow_ids)
        out = [self.decide(t_ms, int(f), int(a), bool(p))
               for f, a, p in zip(flow_ids, acquires, prioritized)]
        return [s for s, _ in out], [w for _, w in out]


def for_deployment(dep, lower_precision: bool = False) -> Reference:
    e = dep.spec["engine"]
    return Reference(dep.reference_rules(), dep.ns_max_qps,
                     int(e["bucket_ms"]), int(e["n_buckets"]),
                     occupy_timeout_ms=dep.occupy_timeout_ms,
                     lower_precision=lower_precision)
