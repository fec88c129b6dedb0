"""Mean time the device lane stood blocked on ``max_device_inflight`` before a
dispatch: the server's ``permit_wait_ms``
phase histogram over the whole window. None where the program has no such
histogram (a tree from before PR 24)."""

NAME = "lane.permit_wait_avg_ms"
UNIT = "ms"
LAYER = "device lane"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a = snap["before"]["stages"].get("permit_wait_ms")
    b = snap["after"]["stages"].get("permit_wait_ms")
    if a is None or b is None or b["count"] - a["count"] <= 0:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
