"""How late the open-loop generator sent its frames: actual minus due send
time, 99th percentile. A starved generator invalidates the latencies."""

NAME = "client.send_lag_p99_ms"
UNIT = "ms"
LAYER = "client"
MOVES = "verdict_latency_p95_ms"
SOURCE = "host_clock"


def reduce(snap):
    import numpy as np

    lag = snap["client"]["lag_s"]
    if lag.size == 0:
        return None  # closed loop: nothing is due
    return float(np.percentile(lag, 99)) * 1e3
