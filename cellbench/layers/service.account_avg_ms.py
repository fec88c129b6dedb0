"""Mean time per dispatch of the always-on accounting (namespace attribution,
verdict counters, SLO plane, timeline, stat log, breaker scan): the server's ``account_ms``
phase histogram over the whole window. None where the program has no such
histogram (a tree from before PR 24)."""

NAME = "service.account_avg_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a = snap["before"]["stages"].get("account_ms")
    b = snap["after"]["stages"].get("account_ms")
    if a is None or b is None or b["count"] - a["count"] <= 0:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
