"""Mean time a dispatched group waits between the device lane's handoff and a
reply lane picking it up: the server's ``reply_queue_wait_ms``
phase histogram over the whole window. None where the program has no such
histogram (a tree from before PR 24)."""

NAME = "lane.reply_queue_wait_avg_ms"
UNIT = "ms"
LAYER = "device lane"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a = snap["before"]["stages"].get("reply_queue_wait_ms")
    b = snap["after"]["stages"].get("reply_queue_wait_ms")
    if a is None or b is None or b["count"] - a["count"] <= 0:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
