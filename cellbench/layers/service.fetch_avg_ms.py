"""Mean time per dispatch to unpack the one verdict buffer that is already on
the host (no copy from the device since PR 25): the padding sliced off, one
unsort to request order, the MOVED overlay: the server's ``fetch_ms``
phase histogram over the whole window. None where the program has no such
histogram (a tree from before PR 24)."""

NAME = "service.fetch_avg_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a = snap["before"]["stages"].get("fetch_ms")
    b = snap["after"]["stages"].get("fetch_ms")
    if a is None or b is None or b["count"] - a["count"] <= 0:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
