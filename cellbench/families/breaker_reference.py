"""The breaker family's plain reference: what a token server must answer when
some of its flows carry a circuit breaker and its clients report how their
calls went.

One request and one completion at a time, in arrival order, in plain Python
ints and floats (float64). It imports nothing of the program and takes
nothing the program made; under the breakers lie ``flow_reference.py``'s
window, namespace guard and pacing, used from there. Each flow's statistics
are a dictionary of bucket start -> ``[total, slow, error]``. Written from
the published breakers of the reference implementation:

``AbstractCircuitBreaker`` (``tryPass``, ``fromCloseToOpen``,
``fromOpenToHalfOpen``, ``fromHalfOpenToClose`` and the rollback to OPEN)
    one CLOSED / OPEN / HALF_OPEN machine per resource. ``tryPass``: CLOSED
    passes; OPEN passes one request as the probe once ``recoveryTimeoutMs``
    has gone by and refuses the rest; HALF_OPEN refuses. ``onRequestComplete``
    in HALF_OPEN: a good completion closes and resets the statistics, a bad
    one opens again for another ``recoveryTimeoutMs``.
``ResponseTimeCircuitBreaker.onRequestComplete``
    SLOW_REQUEST_RATIO: a completion is slow when ``rt > maxAllowedRt``;
    CLOSED opens when, over ``statIntervalMs``, ``total >= minRequestAmount``
    and ``slow / total > slowRatioThreshold``.
``ExceptionCircuitBreaker.onRequestComplete``
    ERROR_RATIO: ``errors / total > count``; ERROR_COUNT: ``errors > count``;
    both with ``total >= minRequestAmount``. Every comparison is strict.

Departures from the Java, each because the token server holds the machines
of every client on a device, decides a frame at a time on a millisecond clock
of its own, and learns of completions by report (``docs/DEGRADE.md`` states
them as the device's semantics):

* **bucketed stat window**: a completion counts in the ``bucket_ms`` bucket
  of its *ingest* (the server's clock when its report arrives, not when the
  call ended); a request at ``t`` reads the buckets that started in
  ``(t - n_buckets * bucket_ms, t]`` and not before ``t - stat_interval_ms``.
* **lazy evaluation at decide time**: the threshold is looked at when a
  request comes, not when a completion lands. A CLOSED flow opens at the
  first decide step that finds its window over the threshold, and that step
  refuses *every* row of the flow it holds (upstream refuses from the request
  after the crossing completion). One arrival time per frame, rows decided in
  order: the probe ticket goes to the flow's first row of the step that finds
  the recovery timeout gone by; the flow's other rows there, and every row
  until a completion resolves the probe, are refused. A probe nobody reports
  on is given again after another ``recovery_timeout_ms``.
* **the fence at ``opened_ms``**: a transition at ``t0`` to OPEN or to CLOSED
  hides every bucket that starts before ``t0``: upstream's ``resetStat()`` at
  bucket granularity. The rest of ``t0``'s own bucket is never counted.
* **reports for unadmitted rows**: any completion of the flow resolves a live
  probe, the first one ingested after the ticket was given, whichever row it
  belongs to, and a client may report on a row that was refused (the
  benchmark's generators do, ``families/breaker.py``). SLOW_REQUEST_RATIO
  judges the resolving completion by its ``rt``, the other two by its
  exception flag.
* **retry-after**: a refused row carries in ``remaining`` the milliseconds
  until a probe may pass: the whole timeout on the step that opens the flow
  and for the rows behind a probe in its step, else what is left of it.
* rows the namespace guard refuses never reach a breaker; a DEGRADED row
  takes no token and is counted by the guard (it arrived).

``lower_precision=True`` is the control: the same reference with every
running total (window sums, completions, slow ones, errors) rounded to 8
significant bits. Counts and completions past 256 then go wrong, which the
comparison has to see.
"""

from __future__ import annotations

from cellbench.deploy import (BLOCKED, DEFAULT, DEGRADED, NO_RULE, OK,
                              RATE_LIMITER, SHOULD_WAIT, TOO_MANY)
from cellbench.families.flow_reference import Window, _bf16

SLOW_REQUEST_RATIO, ERROR_RATIO, ERROR_COUNT = 0, 1, 2
CLOSED, OPEN, HALF_OPEN = 0, 1, 2
NEVER = -(1 << 40)


class Breaker:
    """A ``DegradeRule``: how a flow's breaker judges its completions."""

    def __init__(self, strategy: int, threshold: float, slow_rt_ms: int = 0,
                 min_request_amount: int = 5, stat_interval_ms: int = 1000,
                 recovery_timeout_ms: int = 10_000):
        self.strategy, self.threshold = int(strategy), float(threshold)
        self.slow_rt_ms = int(slow_rt_ms)
        self.min_request = int(min_request_amount)
        self.stat_ms = int(stat_interval_ms)
        self.recovery_ms = int(recovery_timeout_ms)

    def bad(self, rt_ms: int, exc: int) -> bool:
        """Whether a completion fails a probe."""
        if self.strategy == SLOW_REQUEST_RATIO:
            return rt_ms > self.slow_rt_ms
        return exc > 0


class _Machine:
    """What one flow's breaker keeps between events."""

    def __init__(self):
        self.state = CLOSED
        self.since = NEVER  # the last move to OPEN or CLOSED: the fence
        self.ticket = NEVER  # when the live probe ticket was given
        self.done = {}  # bucket start -> [total, slow, error]


class Reference:
    def __init__(self, rules, breakers, ns_max_qps: float, bucket_ms: int,
                 n_buckets: int, max_queue_ms: int = 500,
                 lower_precision: bool = False):
        """``rules``: ``{flow_id: (count, namespace, behaviour)}``;
        ``breakers``: ``{flow_id: Breaker}``."""
        self.rules, self.breakers = dict(rules), dict(breakers)
        self.ns_max_qps = float(ns_max_qps)
        self.bucket_ms, self.n_buckets = bucket_ms, n_buckets
        self.interval_ms = bucket_ms * n_buckets
        self.interval_s = self.interval_ms / 1000.0
        self.max_queue_ms = max_queue_ms
        self.round = _bf16 if lower_precision else (lambda x: x)
        self.flow_win, self.ns_win, self.machines = {}, {}, {}
        self.latest = {}  # paced flows: latest passed time, ms
        self.reported = 0  # completions ingested
        # transitions, as the program counts them: trips, probe tickets,
        # probes closed, probes rolled back
        self.moves = {"open": 0, "probe": 0, "close": 0, "rollback": 0}

    def _win(self, table: dict, key) -> Window:
        w = table.get(key)
        if w is None:
            w = table[key] = Window(self.bucket_ms, self.n_buckets)
        return w

    def machine(self, flow_id: int) -> _Machine:
        m = self.machines.get(flow_id)
        if m is None:
            m = self.machines[flow_id] = _Machine()
        return m

    # -- completions -----------------------------------------------------------
    def report(self, t_ms: int, flow_ids, rt_ms, exc) -> None:
        """One report ingested at ``t_ms``: its completions in order."""
        start = t_ms - t_ms % self.bucket_ms
        for f, rt, e in zip(flow_ids, rt_ms, exc):
            f, rt, e = int(f), int(rt), int(e)
            if f not in self.rules:
                continue  # dropped and counted by the server: unknown flow
            self.reported += 1
            br = self.breakers.get(f)
            if br is None:
                continue  # telemetry only: no breaker reads it
            m = self.machine(f)
            cell = m.done.get(start)
            if cell is None:
                cell = m.done[start] = [0, 0, 0]
            cell[0] += 1
            cell[1] += 1 if rt > br.slow_rt_ms else 0
            cell[2] += 1 if e > 0 else 0
            if m.state == HALF_OPEN and m.ticket != NEVER:
                bad = br.bad(rt, e)
                self.moves["rollback" if bad else "close"] += 1
                m.state = OPEN if bad else CLOSED
                m.since, m.ticket = t_ms, NEVER

    def _over_threshold(self, m: _Machine, br: Breaker, t_ms: int) -> bool:
        oldest = t_ms - self.interval_ms  # a bucket this old has left
        if m.done and min(m.done) <= oldest:
            for s in [s for s in m.done if s <= oldest]:
                del m.done[s]
        lo = max(t_ms - br.stat_ms, m.since)
        total = slow = errs = 0
        for s, (n, w, e) in m.done.items():
            if lo <= s <= t_ms:
                total, slow, errs = total + n, slow + w, errs + e
        total, slow, errs = (self.round(float(total)), self.round(float(slow)),
                             self.round(float(errs)))
        if total < br.min_request:
            return False
        if br.strategy == SLOW_REQUEST_RATIO:
            return slow / total > br.threshold
        if br.strategy == ERROR_RATIO:
            return errs / total > br.threshold
        return errs > br.threshold

    # -- requests ----------------------------------------------------------------
    def decide_frame(self, t_ms: int, flow_ids, acquires):
        """``(statuses, remaining)`` of rows that arrive together and are
        decided in order. ``remaining`` is the retry-after of a DEGRADED row,
        the wait of a SHOULD_WAIT row, else 0."""
        status, rest = [], []
        # a flow's fate in this step is decided at its first row: what the
        # machine was when the step began
        fate = {}  # flow_id -> ("pass" | "shed", retry) after its first row
        for f, a in zip(flow_ids, acquires):
            f, a = int(f), int(a)
            rule = self.rules.get(f)
            if rule is None:
                status.append(NO_RULE)
                rest.append(0)
                continue
            count, ns, behaviour = rule
            nsw = self._win(self.ns_win, ns)
            if self.round(nsw.total(t_ms)) + 1.0 > self.round(
                    self.ns_max_qps * self.interval_s):
                status.append(TOO_MANY)
                rest.append(0)
                continue
            nsw.add(t_ms, 1.0)
            br = self.breakers.get(f)
            if br is not None:
                shed = fate.get(f)
                if shed is None:
                    shed = fate[f] = self._first_row(self.machine(f), br, t_ms)
                    if shed[0] == "probe":
                        # this row is the probe; the rows behind it wait
                        fate[f] = ("shed", br.recovery_ms)
                        shed = ("pass", 0)
                if shed[0] == "shed":
                    status.append(DEGRADED)
                    rest.append(shed[1])
                    continue
            s, wait = self._admit(t_ms, f, a, count, behaviour)
            status.append(s)
            rest.append(wait)
        return status, rest

    def _admit(self, t_ms: int, f: int, a: int, count: float, behaviour: int):
        """``flow_reference.Reference.decide`` behind the guard."""
        if behaviour == DEFAULT:
            fw = self._win(self.flow_win, f)
            if self.round(fw.total(t_ms)) + a <= self.round(
                    count * self.interval_s):
                fw.add(t_ms, float(a))
                return OK, 0
            return BLOCKED, 0
        if behaviour == RATE_LIMITER:
            cost = round(1000.0 * a / count)
            latest = max(self.latest.get(f, NEVER), t_ms - cost)
            wait = latest + cost - t_ms
            if wait > self.max_queue_ms:
                return BLOCKED, 0
            self.latest[f] = latest + cost
            return (OK, 0) if wait <= 0 else (SHOULD_WAIT, int(wait))
        raise ValueError(f"behaviour {behaviour} has no reference here")

    def _first_row(self, m: _Machine, br: Breaker, t_ms: int) -> tuple:
        """What a step does with a guarded flow, decided at the flow's first
        row: ``("pass", 0)``, ``("shed", retry)`` or ``("probe", 0)``."""
        if m.state == CLOSED:
            if not self._over_threshold(m, br, t_ms):
                return "pass", 0
            m.state, m.since, m.ticket = OPEN, t_ms, NEVER
            self.moves["open"] += 1
            return "shed", br.recovery_ms
        clock = m.since if m.state == OPEN else m.ticket
        left = clock + br.recovery_ms - t_ms
        if left > 0:
            return "shed", left
        m.state, m.ticket = HALF_OPEN, t_ms
        self.moves["probe"] += 1
        return "probe", 0


def for_deployment(dep, only=None, **control) -> Reference:
    """The reference of a breaker deployment (``families/breaker.py``), of
    ``only`` those flow ids if given."""
    rules = {fid: (count, ns, behaviour)
             for fid, count, ns, behaviour in dep.rules()
             if only is None or fid in only}
    breakers = {fid: Breaker(**kw) for fid, _ns, kw in dep.degrade_rules()
                if only is None or fid in only}
    e = dep.spec["engine"]
    return Reference(rules, breakers, dep.ns_max_qps, int(e["bucket_ms"]),
                     int(e["n_buckets"]), **control)
