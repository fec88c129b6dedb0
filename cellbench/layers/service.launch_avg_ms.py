"""Mean time per dispatch from the service lock acquired to the dispatch
issued: the jitted call (argument transfer and enqueue), the engine clock,
dirty-set bookkeeping and, past the lock since PR 25, the start of the
verdict buffer's copy to the host: the server's ``launch_ms``
phase histogram over the whole window. None where the program has no such
histogram (a tree from before PR 24)."""

NAME = "service.launch_avg_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a = snap["before"]["stages"].get("launch_ms")
    b = snap["after"]["stages"].get("launch_ms")
    if a is None or b is None or b["count"] - a["count"] <= 0:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
