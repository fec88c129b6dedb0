"""1 minus the union of device-operation intervals over the traced slice,
on the median chip, in percent."""

NAME = "device.idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "decided_verdicts_per_s"
SOURCE = "device_trace"


def reduce(snap):
    t = snap["trace"]
    return 100.0 * (1.0 - t["busy_s_median_chip"] / t["window_s"])
