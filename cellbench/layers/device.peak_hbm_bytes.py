"""Peak bytes in use on the fullest chip after the window, as
``device.memory_stats()`` reports it."""

NAME = "device.peak_hbm_bytes"
UNIT = "bytes"
LAYER = "device"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    return float(snap["memory_peak_bytes"]) or None
