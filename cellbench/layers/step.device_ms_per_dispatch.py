"""Device time of the executed programs per dispatch in the traced slice, on
the median chip: XLA Modules time over DEVICE_IN events."""

NAME = "step.device_ms_per_dispatch"
UNIT = "ms"
LAYER = "decide step"
MOVES = "verdict_latency_p50_ms"
SOURCE = "device_trace"


def reduce(snap):
    n = sum(1 for e in snap["events"] if e["stage"] == "device_in")
    if n == 0 or snap["trace"]["module_runs_median_chip"] == 0:
        return None
    return snap["trace"]["module_s_median_chip"] * 1e3 / n
