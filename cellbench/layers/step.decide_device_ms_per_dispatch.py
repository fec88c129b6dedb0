"""Device time of the decide step's own programs per dispatch in the traced
slice, on the median chip: the ``jit_decide*`` entries of the trace's
programs over DEVICE_IN events. ``step.device_ms_per_dispatch`` counts every
program the device ran; this one needs the names PR 24 gave the steps and is
None where the trace holds none (``jit__unknown`` on an older tree)."""

NAME = "step.decide_device_ms_per_dispatch"
UNIT = "ms"
LAYER = "decide step"
MOVES = "verdict_latency_p50_ms"
SOURCE = "device_trace"


def reduce(snap):
    n = sum(1 for e in snap["events"] if e["stage"] == "device_in")
    seconds = [s for name, s in snap["trace"]["modules"]
               if str(name).startswith("jit_decide")]
    if n == 0 or not seconds:
        return None
    return sum(seconds) * 1e3 / n
