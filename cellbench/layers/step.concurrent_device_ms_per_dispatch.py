"""Device time of the concurrency step's own programs per concurrency
dispatch in the traced slice, on the median chip: the ``jit_concurrent_step*``
entries of the trace's programs (the timer's idle ticks among them, of which
a loaded window has next to none) over the DEVICE_IN events a concurrency
dispatch marks (``shard`` 2). None where the trace holds no such program or
the recorder no such event (a tree from before PR 41)."""

NAME = "step.concurrent_device_ms_per_dispatch"
UNIT = "ms"
LAYER = "concurrent step"
MOVES = "verdict_latency_p50_ms"
SOURCE = "device_trace"


def reduce(snap):
    from cellbench import concurrent_roofline

    n = concurrent_roofline.concurrent_dispatches(snap)
    seconds = concurrent_roofline.concurrent_program_seconds(snap)
    if n == 0 or seconds <= 0:
        return None
    return seconds * 1e3 / n
