"""Mean time of a frame from its verdicts' submission to the door
(``sn_fd_submit`` entered) to ``send()`` having taken the last byte of its
reply (outbox, eventfd wake, the IO thread's turn, ``EPOLLOUT`` stalls): the
native door's ``door_out_ms`` histogram, counted per frame on the IO thread,
over the whole window. None where the program has no such histogram (a tree
from before PR 38) or no reply went out."""

NAME = "door.submit_to_wire_avg_ms"
UNIT = "ms"
LAYER = "door reply"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a = snap["before"]["stages"].get("door_out_ms")
    b = snap["after"]["stages"].get("door_out_ms")
    if a is None or b is None or b["count"] - a["count"] <= 0:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
