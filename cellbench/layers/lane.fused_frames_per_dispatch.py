"""Engine frames folded into fused dispatches per dispatch of the window:
``fused_frames_total`` over the ``dispatch_ms`` count. 0 when nothing fuses."""

NAME = "lane.fused_frames_per_dispatch"
UNIT = "frames"
LAYER = "device lane"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"], snap["after"]
    n = b["stages"]["dispatch_ms"]["count"] - a["stages"]["dispatch_ms"]["count"]
    if n <= 0:
        return None
    return (b["fused_frames"] - a["fused_frames"]) / n
