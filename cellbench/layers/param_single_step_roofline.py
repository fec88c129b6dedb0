"""Share of its roofline the hot-parameter step reaches when every request
is a frame of its own: the least time the chip could take for the real rows
of the param dispatches of the traced slice (cellbench/param_roofline.py,
which pads each dispatch's packed input and output to its serve bucket and
counts the cells on the rows) over the device time of the
``jit_param_decide*`` programs, as ``param_step_roofline`` reads it where
the rows come in batch frames. No kernel is this cell's own: what the share
shows is the price of a pull of a dozen rows padded to the 64-row bucket.
None where there is nothing to read."""

NAME = "param_single_step_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "decided_verdicts_per_s"
SOURCE = "device_trace"


def reduce(snap):
    from cellbench import param_roofline

    if "param" not in snap["config"]:
        return None
    rows = param_roofline.param_dispatch_rows(snap)
    spent = param_roofline.param_program_seconds(snap)
    if not rows or spent <= 0:
        return None
    peaks = snap["peaks"].get(snap["device_kind"])
    if peaks is None:
        raise KeyError(f"no peaks for device_kind {snap['device_kind']!r}")
    return 100.0 * param_roofline.least_seconds(
        rows, snap["slice_s"], snap["config"], peaks) / spent
