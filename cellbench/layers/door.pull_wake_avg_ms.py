"""Mean time of a pull from its return in C (the door's own stamp, taken just
before the call goes back to ``ctypes``) to the intake lane running again in
Python: the ``ctypes`` return and the wait for the GIL, the server's
``door_wake_ms`` histogram over the whole window. The program's GIL gauge: a
lane that has finished its C call and cannot run. None where the program has
no such histogram (a tree from before PR 38) or nothing was pulled."""

NAME = "door.pull_wake_avg_ms"
UNIT = "ms"
LAYER = "door intake"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a = snap["before"]["stages"].get("door_wake_ms")
    b = snap["after"]["stages"].get("door_wake_ms")
    if a is None or b is None or b["count"] - a["count"] <= 0:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
