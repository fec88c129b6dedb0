"""The plain reference of cluster concurrency limiting: scalar Python, a dict
of ``held``, a dict of tokens, rows in order, a clock passed in. It imports
nothing of the program under test.

Upstream: ``ConcurrentClusterFlowChecker`` (check-and-add of ``nowCalls``
against the rule's count), ``CurrentConcurrencyManager`` (``nowCalls`` per
flow), ``TokenCacheNodeManager`` (the issued ids), ``RegularExpireStrategy``
(tokens of dead clients reclaimed after ``resourceTimeout``). The guarantees,
which the configuration ``concurrent-mesh-100k`` states in its own words:

(a) An acquire of ``count`` on flow ``f`` passes iff ``held[f] + count <=
    level[f]`` at its turn, rows taking their turns in row order. A passed
    row is answered OK with a token id that is non-zero and was never issued
    before, and ``remaining = level - held`` after it; a refused row BLOCKED
    with id 0 and the headroom left; an unknown flow NO_RULE; ``count <= 0``
    FAIL.
(b) A release of a live id lowers ``held`` of the id's flow by the id's
    count, frees the token and answers RELEASE_OK; of any other id
    (released, expired, never issued, 0) ALREADY_RELEASE, and nothing
    changes. The same id twice: RELEASE_OK once.
(c) A token not released is reclaimed once ``resource_timeout_ms`` have
    passed since it was issued (:meth:`expire`, which whoever drives the
    reference calls with the clock: the system under test may take a stated
    slack longer); ``held`` falls by its count.
(d) Order is the caller's: rows are applied as they are handed in.
(e) ``held[f]`` is at all times the sum of the counts of ``f``'s live
    tokens (:meth:`check`).
(f) Every row is answered exactly once.
"""

from __future__ import annotations

OK, BLOCKED, NO_RULE, FAIL = 0, 1, 3, 5
RELEASE_OK, ALREADY_RELEASE = 6, 7


class Reference:
    def __init__(self, levels: dict, timeouts_ms, ):
        """``levels``: ``{flow id: level}``; ``timeouts_ms`` one timeout for
        every flow or ``{flow id: ms}``."""
        self.levels = dict(levels)
        self.timeouts = timeouts_ms
        self.held = {}
        self.tokens = {}  # id -> (flow, count, expires at)
        self.next_id = 1

    def timeout_of(self, flow: int) -> int:
        t = self.timeouts
        return int(t[flow] if isinstance(t, dict) else t)

    def acquire(self, now_ms: int, flow: int, count: int = 1):
        """``(status, remaining, token id)`` of one acquire row."""
        level = self.levels.get(flow)
        if level is None:
            return NO_RULE, 0, 0
        if count <= 0:
            return FAIL, max(0, level - self.held.get(flow, 0)), 0
        held = self.held.get(flow, 0)
        if held + count > level:
            return BLOCKED, max(0, level - held), 0
        self.held[flow] = held + count
        token = self.next_id
        self.next_id += 1
        self.tokens[token] = (flow, count, now_ms + self.timeout_of(flow))
        return OK, max(0, level - held - count), token

    def release(self, token: int) -> int:
        node = self.tokens.pop(token, None)
        if node is None:
            return ALREADY_RELEASE
        self._lower(node)
        return RELEASE_OK

    def expire(self, now_ms: int) -> int:
        """Reclaim every token whose time has come; returns how many."""
        due = [t for t, (_f, _c, at) in self.tokens.items() if at <= now_ms]
        for t in due:
            self._lower(self.tokens.pop(t))
        return len(due)

    def _lower(self, node) -> None:
        flow, count, _at = node
        self.held[flow] = self.held.get(flow, 0) - count

    # -- frames ---------------------------------------------------------------
    def acquire_frame(self, now_ms: int, flows, counts):
        """Rows of one acquire frame, in row order: three lists."""
        out = [self.acquire(now_ms, int(f), int(c))
               for f, c in zip(flows, counts)]
        return ([o[0] for o in out], [o[1] for o in out],
                [o[2] for o in out])

    def release_frame(self, tokens) -> list:
        return [self.release(int(t)) for t in tokens]

    def check(self) -> None:
        """Invariant (e), and ``held <= level`` for every ruled flow."""
        total = {}
        for flow, count, _at in self.tokens.values():
            total[flow] = total.get(flow, 0) + count
        for flow, held in self.held.items():
            assert held == total.get(flow, 0), (flow, held, total.get(flow))
            assert held <= self.levels.get(flow, held), (flow, held)


def for_deployment(dep, only=None) -> Reference:
    """The reference of a ``concurrent`` deployment's rules (``only``: a
    set of flow ids to keep)."""
    levels = {fid: level for fid, level, _ns in dep.rules()
              if only is None or fid in only}
    return Reference(levels, dep.resource_timeout_ms)
