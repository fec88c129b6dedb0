"""Shared by the readers of ``door_residence_ms`` (a file whose name starts
with ``_`` is no reader: ``manifest.Cell.readers`` skips it)."""


def window_quantile(a, b, q):
    """The q-quantile of a histogram over a window, from its cumulative
    bucket counts before (``a``) and after (``b``), interpolated inside the
    target bucket as ``LatencyHistogram.quantile`` does. ``max`` is the
    histogram's since the program started (or since it was reset), not the
    window's: it bounds the overflow bucket, and clips a bounded bucket
    only where it lies inside it."""
    le = b["le"]
    cum = [y - x for x, y in zip(a["cum"], b["cum"])]
    total = cum[-1]
    if total <= 0:
        return None
    vmax = b["max"]
    rank, below = q * total, 0
    for i, upto in enumerate(cum):
        if upto > below and upto >= rank:
            lo = 0.0 if i == 0 else le[i - 1]
            hi = vmax if i == len(le) else le[i]
            hi = min(hi, vmax) if vmax > 0 else hi
            if hi <= lo:
                return hi
            return lo + (hi - lo) * (rank - below) / (upto - below)
        below = upto
    return vmax
