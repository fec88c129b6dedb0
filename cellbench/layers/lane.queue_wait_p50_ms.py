"""Median wait of a frame between the intake's hand-over and its dispatch:
flight recorder ENQUEUE -> DISPATCH of sampled frames in the traced slice."""

NAME = "lane.queue_wait_p50_ms"
UNIT = "ms"
LAYER = "device lane"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_span"


def reduce(snap):
    import numpy as np

    enq, waits = {}, []
    for e in snap["events"]:
        if e["stage"] == "enqueue":
            enq[e["xid"]] = e["t_ns"]
        elif e["stage"] == "dispatch" and e["xid"] in enq:
            waits.append(e["t_ns"] - enq.pop(e["xid"]))
    if not waits:
        return None
    return float(np.median(waits)) / 1e6
