"""Shared by the readers of the device lane's counters of its turn between
kinds (``lane_*_total``, PR 49; a file whose name starts with ``_`` is no
reader: ``manifest.Cell.readers`` skips it)."""

KINDS = ("flow", "param", "concurrent")  # ServerMetrics.LANE_KINDS


def grew(snap, names):
    """What each counter of ``names`` grew by over the window, or None where
    a snapshot lacks one (a tree from before PR 49)."""
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    if any(n not in s for n in names for s in (a, b)):
        return None
    return [b[n] - a[n] for n in names]


def share(snap, part, whole, scale=1.0):
    """``scale`` x the growth of ``part`` over the summed growth of
    ``whole``; None where a counter is missing or ``whole`` stood still."""
    d = grew(snap, [part, *whole])
    if d is None or sum(d[1:]) <= 0:
        return None
    return scale * d[0] / sum(d[1:])
