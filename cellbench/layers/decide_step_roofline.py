"""Share of its roofline the decide step reaches: the least time the chip
could take for the dispatches of the traced slice (cellbench/roofline.py,
per dispatch at its serve bucket, peaks by device_kind) over the device
time of the executed programs. Only for mixes whose acquire is uniform:
the analytic model covers the uniform grouped step alone."""

NAME = "decide_step_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "decided_verdicts_per_s"
SOURCE = "device_trace"


def reduce(snap):
    from cellbench import roofline

    if len(snap["traffic"]["acquire"]["values"]) != 1:
        return None  # the refine path is not modelled
    peaks = snap["peaks"].get(snap["device_kind"])
    if peaks is None:
        raise KeyError(f"no peaks for device_kind {snap['device_kind']!r}")
    rows = [e["aux"] for e in snap["events"] if e["stage"] == "device_in"]
    spent = snap["trace"]["module_s_median_chip"]
    if not rows or spent <= 0:
        return None
    least = sum(roofline.least_seconds(n, snap["config"], peaks)
                for n in rows)
    return 100.0 * least / spent
