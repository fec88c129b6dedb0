"""Mean time of a frame from the ``recv()`` that read its last byte to the
pull that took it about to return to the intake lane (decode, the wait in the
arena, the copy to staging): the native door's ``door_in_ms`` histogram,
counted per frame on the door's own threads, over the whole window. None
where the program has no such histogram (a tree from before PR 38) or no
frame came in."""

NAME = "door.rx_to_pull_avg_ms"
UNIT = "ms"
LAYER = "door intake"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a = snap["before"]["stages"].get("door_in_ms")
    b = snap["after"]["stages"].get("door_in_ms")
    if a is None or b is None or b["count"] - a["count"] <= 0:
        return None
    return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
