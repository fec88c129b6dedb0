"""The plain reference of hot-parameter limiting: exact counts per (rule,
value) in a sliding window, no sketch and no hashing of the program's.

A rule limits every value of its parameter to ``count`` tokens per window
(``n_buckets`` buckets of ``bucket_ms``); a value named by one of the rule's
items carries the item's threshold instead. The window of a value is a
dictionary ``bucket start -> tokens``; at time ``t`` it spans the bucket ``t``
falls in and the ``n_buckets - 1`` before it. Requests are decided in order.
A request of several values passes only if every value has headroom for the
acquire; the values that had headroom stay counted when another value blocks
the request (``DefaultTokenService.request_params_batch`` documents the
same). Pure Python: imports nothing of the program.
"""

from __future__ import annotations

from cellbench.deploy import BLOCKED, NO_RULE, OK


class Reference:
    def __init__(self, rules, bucket_ms: int, n_buckets: int):
        """``rules``: ``{rule id: (count, {value: threshold})}``; a value is
        whatever names it on the wire (its 64-bit hash)."""
        self.rules = dict(rules)
        self.bucket_ms, self.n_buckets = int(bucket_ms), int(n_buckets)
        self.windows = {}  # (rule, value) -> {bucket start: tokens}

    def total(self, key, t_ms: int) -> float:
        """Tokens counted under ``key`` in the window at ``t_ms``; buckets
        that slid out are dropped on the way."""
        w = self.windows.setdefault(key, {})
        oldest = (t_ms - t_ms % self.bucket_ms
                  - (self.n_buckets - 1) * self.bucket_ms)
        for start in [s for s in w if s < oldest]:
            del w[start]
        return sum(w.values())

    def decide(self, t_ms: int, rule: int, acquire: int, values) -> int:
        entry = self.rules.get(rule)
        if entry is None:
            return NO_RULE
        count, items = entry
        every = True
        for v in values:
            v = int(v)
            key = (rule, v)
            if self.total(key, t_ms) + acquire <= items.get(v, count):
                w = self.windows[key]
                start = t_ms - t_ms % self.bucket_ms
                w[start] = w.get(start, 0) + acquire
            else:
                every = False
        return OK if every else BLOCKED

    def decide_all(self, t_ms: int, rules, acquires, values) -> list:
        """Requests in order, all at ``t_ms``: ``values[i]`` are the values
        of request ``i``."""
        return [self.decide(t_ms, int(r), int(a), v)
                for r, a, v in zip(rules, acquires, values)]


def for_deployment(dep) -> Reference:
    return Reference({r: (count, dict(items))
                      for r, count, items in dep.rules()},
                     dep.bucket_ms, dep.window_ms // dep.bucket_ms)
