"""Mean time a reply lane waits for a dispatch's verdicts (device step plus
materialise): the server's ``decide_ms`` stage histogram over the window."""

NAME = "service.decide_avg_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"]["decide_ms"], snap["after"]["stages"]["decide_ms"]
    n = b["count"] - a["count"]
    if n <= 0:
        return None
    return (b["sum"] - a["sum"]) / n
