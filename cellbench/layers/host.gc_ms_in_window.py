"""Milliseconds the server process spent in collections of Python's garbage
collector that took over 20 ms each, inside the window (``gc.callbacks``).
Every such collection holds the GIL, so every lane stands still for it."""

NAME = "host.gc_ms_in_window"
UNIT = "ms"
LAYER = "service"
MOVES = "verdict_latency_p95_ms"
SOURCE = "host_clock"


def reduce(snap):
    stalls = snap.get("stalls")
    if not stalls:
        return None
    return 1e3 * stalls["gc_total_s"]
