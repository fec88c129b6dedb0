"""Share of its roofline the hot-parameter step reaches: the least time the
chip could take for the param dispatches of the traced slice
(cellbench/param_roofline.py: the cells their rows gather and scatter, the
packed input and output, a stale bucket's plane once per bucket_ms; peaks by
device_kind) over the device time of the ``jit_param_decide*`` programs.
None where there is nothing to read."""

NAME = "param_step_roofline"
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
    least = param_roofline.least_seconds(rows, snap["slice_s"],
                                         snap["config"], peaks)
    return 100.0 * least / spent
