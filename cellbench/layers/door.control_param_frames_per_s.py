"""Single PARAM_FLOW frames (type 2) the control loop answered, a second of
the window: the program's ``param_control_frames_total``, after the window
less before it, over the time between the two readings. 0 where every such
frame is served on the door's data plane. None where the program has no such
counter (a tree from before the control loop counted them)."""

NAME = "door.control_param_frames_per_s"
UNIT = "1/s"
LAYER = "control lane"
MOVES = "verdict_latency_p95_ms"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"], snap["after"]
    for c in (a, b):
        if "param_control_frames_total" not in c["stages"]:
            return None
    seconds = b["t"] - a["t"]
    if seconds <= 0:
        return None
    return (b["stages"]["param_control_frames_total"]
            - a["stages"]["param_control_frames_total"]) / seconds
