"""Device time of the outcome step's own programs per outcome step in the
traced slice, on the median chip: the ``jit_outcome_step*`` entries of the
trace's programs over the ``outcome`` events of the flight recorder that
scattered any row. None where the trace holds no such program or the
recorder no such event."""

NAME = "step.outcome_device_ms_per_step"
UNIT = "ms"
LAYER = "outcome step"
MOVES = "verdict_latency_p50_ms"
SOURCE = "device_trace"


def reduce(snap):
    from cellbench import outcome_roofline

    n = len(outcome_roofline.ingest_rows(snap))
    seconds = outcome_roofline.outcome_program_seconds(snap)
    if n == 0 or seconds <= 0:
        return None
    return seconds * 1e3 / n
