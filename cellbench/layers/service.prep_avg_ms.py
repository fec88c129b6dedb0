"""Mean host prep per dispatch before the service lock (asarray, uniform test,
bucket pick, slot lookup and grouping sort, step lookup): the server's ``prep_ms``
phase histogram over the whole window. None where the program has no such
histogram (a tree from before PR 24)."""

NAME = "service.prep_avg_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a = snap["before"]["stages"].get("prep_ms")
    b = snap["after"]["stages"].get("prep_ms")
    if a is None or b is None or b["count"] - a["count"] <= 0:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
