"""Verdict latency, 99th percentile over decided rows of the whole window
(a per-layer metric until its spread is known)."""

NAME = "client.latency_p99_ms"
UNIT = "ms"
LAYER = "client"
MOVES = "verdict_latency_p95_ms"
SOURCE = "host_clock"


def reduce(snap):
    import numpy as np

    c = snap["client"]
    if c["lat_s"].size == 0:
        return None
    order = np.argsort(c["lat_s"], kind="stable")
    cum = np.cumsum(c["lat_w"][order])
    at = np.searchsorted(cum, 0.99 * cum[-1], side="left")
    return float(c["lat_s"][order][at]) * 1e3
