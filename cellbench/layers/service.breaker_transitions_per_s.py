"""Breaker transitions a second of the window: flows tripped CLOSED to OPEN
and probe tickets given (what the decide step's breaker arm says it did) and
HALF_OPEN breakers closed and rolled back to OPEN (what the outcome step's
tally says), the program's ``breaker_to_open_total``,
``breaker_probe_tickets_total``, ``breaker_to_closed_total`` and
``breaker_reopened_total``, after the window less before it, over its
seconds. It is what the health script predicts
(``cellbench/tests/test_breaker.py``). None where the program counts none of
it (a tree from before the breaker family)."""

NAME = "service.breaker_transitions_per_s"
UNIT = "1/s"
LAYER = "service"
MOVES = "verdict_latency_p95_ms"
SOURCE = "program_counter"

_COUNTERS = ("breaker_to_open_total", "breaker_probe_tickets_total",
             "breaker_to_closed_total", "breaker_reopened_total")


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    if any(c not in a or c not in b for c in _COUNTERS):
        return None
    seconds = float(snap.get("seconds") or 0.0)
    if seconds <= 0:
        return None
    return sum(b[c] - a[c] for c in _COUNTERS) / seconds
