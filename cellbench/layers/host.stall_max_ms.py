"""The longest time in the window in which the server process stood still:
the longer of the stall watch's longest ticker gap (no Python thread could
run) and its longest time with no dispatch finished (over 0.4 s only).
A stall over the server's 1 s age shed fails the rows that waited."""

NAME = "host.stall_max_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "verdict_latency_p95_ms"
SOURCE = "host_clock"


def reduce(snap):
    stalls = snap.get("stalls")
    if not stalls:
        return None
    return 1e3 * max(stalls["gap_max_s"], stalls["stall_max_s"])
