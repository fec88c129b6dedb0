"""Mean wait for the token service's lock per dispatch: the server's ``lock_wait_ms``
phase histogram over the whole window. None where the program has no such
histogram (a tree from before PR 24)."""

NAME = "service.lock_wait_avg_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "verdict_latency_p95_ms"
SOURCE = "program_counter"


def reduce(snap):
    a = snap["before"]["stages"].get("lock_wait_ms")
    b = snap["after"]["stages"].get("lock_wait_ms")
    if a is None or b is None or b["count"] - a["count"] <= 0:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
