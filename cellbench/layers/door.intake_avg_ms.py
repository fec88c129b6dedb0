"""Mean time of one intake pull (decode, copy, hand-over to the device lane):
the server's ``intake_ms`` stage histogram over the window."""

NAME = "door.intake_avg_ms"
UNIT = "ms"
LAYER = "door intake"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"]["intake_ms"], snap["after"]["stages"]["intake_ms"]
    n = b["count"] - a["count"]
    if n <= 0:
        return None
    return (b["sum"] - a["sum"]) / n
