"""Device time of the decide step's own programs per *flow* dispatch in the
traced slice, on the median chip: the ``jit_decide*`` entries of the trace's
programs over the DEVICE_IN events that are not a hot-parameter dispatch's
(``shard`` 1) nor a concurrency dispatch's (``shard`` 2).
``step.decide_device_ms_per_dispatch`` divides by every DEVICE_IN event and
so under-reads wherever a trace holds a second kind; in a trace of flow
dispatches alone the two agree. None where the trace holds no such program
(``jit__unknown`` on an older tree) or the recorder no flow dispatch."""

NAME = "step.decide_device_ms_per_flow_dispatch"
UNIT = "ms"
LAYER = "decide step"
MOVES = "verdict_latency_p50_ms"
SOURCE = "device_trace"

OTHER_LANES = (1, 2)  # trace.ring.PARAM_LANE, CONCURRENT_LANE


def reduce(snap):
    n = sum(1 for e in snap["events"] if e["stage"] == "device_in"
            and e.get("shard") not in OTHER_LANES)
    seconds = [s for name, s in snap["trace"]["modules"]
               if str(name).startswith("jit_decide")]
    if n == 0 or not seconds:
        return None
    return sum(seconds) * 1e3 / n
