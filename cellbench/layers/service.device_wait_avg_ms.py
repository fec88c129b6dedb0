"""Mean time a materializer waits until the one packed verdict buffer
(``int32[3, rows]``, PR 25) is on the host: what is left of the device step
and of the copy started at launch: the server's ``device_wait_ms``
phase histogram over the whole window. None where the program has no such
histogram (a tree from before PR 24)."""

NAME = "service.device_wait_avg_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a = snap["before"]["stages"].get("device_wait_ms")
    b = snap["after"]["stages"].get("device_wait_ms")
    if a is None or b is None or b["count"] - a["count"] <= 0:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
