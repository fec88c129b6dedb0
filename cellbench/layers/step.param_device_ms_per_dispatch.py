"""Device time of the hot-parameter step's own programs per param dispatch
in the traced slice, on the median chip: the ``jit_param_decide*`` entries of
the trace's programs over the DEVICE_IN events a param dispatch marks
(``shard`` 1). None where the trace holds no such program or the recorder no
such event (a tree from before PR 27)."""

NAME = "step.param_device_ms_per_dispatch"
UNIT = "ms"
LAYER = "param step"
MOVES = "verdict_latency_p50_ms"
SOURCE = "device_trace"


def reduce(snap):
    from cellbench import param_roofline

    n = len(param_roofline.param_dispatch_rows(snap))
    seconds = param_roofline.param_program_seconds(snap)
    if n == 0 or seconds <= 0:
        return None
    return seconds * 1e3 / n
