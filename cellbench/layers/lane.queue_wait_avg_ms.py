"""Mean wait of a pull from its hand-over by the intake lane (where
``intake_ms`` ends) to the start of the ``dispatch_ms`` that took it (the
fusion collect and the concatenation of a fused group included): the
server's ``queue_wait_ms`` histogram over the whole window, one record per
pull. None where the native lane does not record it (a tree from before
PR 38 leaves the series empty) or nothing was pulled."""

NAME = "lane.queue_wait_avg_ms"
UNIT = "ms"
LAYER = "device lane"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a = snap["before"]["stages"].get("queue_wait_ms")
    b = snap["after"]["stages"].get("queue_wait_ms")
    if a is None or b is None or b["count"] - a["count"] <= 0:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
