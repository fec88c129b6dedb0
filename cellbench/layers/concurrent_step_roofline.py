"""Share of its roofline the concurrency step reaches: the least time the
chip could take for what the window's counters say was done
(cellbench/concurrent_roofline.py: a token slot and a gauge cell a release,
a gauge and a level cell an acquire, a gauge cell and a token slot an
admitted row; peaks by device_kind), scaled from the window to the traced
slice, over the device time of the ``jit_concurrent_step*`` programs in the
slice. None where there is nothing to read."""

NAME = "concurrent_step_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "decided_verdicts_per_s"
SOURCE = "device_trace"


def reduce(snap):
    from cellbench import concurrent_roofline

    moved = concurrent_roofline.window_counts(snap)
    spent = concurrent_roofline.concurrent_program_seconds(snap)
    if moved is None or spent <= 0 or snap["seconds"] <= 0:
        return None
    peaks = snap["peaks"].get(snap["device_kind"])
    if peaks is None:
        raise KeyError(f"no peaks for device_kind {snap['device_kind']!r}")
    least = concurrent_roofline.least_seconds(moved, peaks)
    return 100.0 * least * (snap["slice_s"] / snap["seconds"]) / spent
