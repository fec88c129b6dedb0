"""Share of its roofline the outcome step reaches: the least time the chip
could take for the ingests of the traced slice (cellbench/outcome_roofline.py:
the step's arguments at their rung, the cells a row adds into, the breaker
columns it reads, the slab of a stale bucket once per bucket_ms; peaks by
device_kind) over the device time of the ``jit_outcome_step*`` programs.
None where there is nothing to read."""

NAME = "outcome_step_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "decided_verdicts_per_s"
SOURCE = "device_trace"


def reduce(snap):
    from cellbench import outcome_roofline

    rows = outcome_roofline.ingest_rows(snap)
    spent = outcome_roofline.outcome_program_seconds(snap)
    if not rows or spent <= 0:
        return None
    peaks = snap["peaks"].get(snap["device_kind"])
    if peaks is None:
        raise KeyError(f"no peaks for device_kind {snap['device_kind']!r}")
    least = outcome_roofline.least_seconds(rows, snap["slice_s"],
                                           snap["config"], peaks)
    return 100.0 * least / spent
