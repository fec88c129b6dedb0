"""The part of the median verdict latency that is not the server's: the
client's p50 over decided rows of the whole window (weighted, as
``run.e2e_metrics`` computes ``verdict_latency_p50_ms``) less the median of
the server's own residence, last byte in to last byte out
(``door.residence_p50_ms``: the window's ``door_residence_ms`` by difference of
its cumulative bucket counts). What is left is the generator, the kernel's
sockets and the wire. On one machine over loopback a reading over 0.5 ms
makes the generator the finding. None where the program has no residence
histogram (a tree from before PR 38) or the window has no latency."""

NAME = "client.outside_server_p50_ms"
UNIT = "ms"
LAYER = "client"
MOVES = "verdict_latency_p50_ms"
SOURCE = "host_clock"


def reduce(snap):
    import numpy as np

    from cellbench.layers._window import window_quantile

    a = snap["before"]["stages"].get("door_residence_ms")
    b = snap["after"]["stages"].get("door_residence_ms")
    c = snap["client"]
    if (a is None or b is None or "cum" not in a or "cum" not in b
            or c["lat_s"].size == 0):
        return None
    inside = window_quantile(a, b, 0.5)
    if inside is None:
        return None
    order = np.argsort(c["lat_s"], kind="stable")
    cum = np.cumsum(c["lat_w"][order])
    at = np.searchsorted(cum, 0.5 * cum[-1], side="left")
    return float(c["lat_s"][order][at]) * 1e3 - inside
