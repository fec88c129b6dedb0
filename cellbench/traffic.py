"""The general part of the traffic generator. A mix is a data file of
parameters (``<a path>/traffic/<mix>.json``); nothing here knows a mix by
name, and what a row is belongs to the deployment's family
(``cellbench/families/``), whose ``Mix`` draws the rows.

Parameters of every mix:

    loop        "open" (absolute schedule at ``rate_rows_per_s``) or "closed"
                (``connections`` x ``outstanding`` frames kept in flight)
    msg         "batch" (frames of ``frame_rows`` rows) or "single" (one-row
                frames)
    processes, connections, inflight_window_frames, timeout_ms, trace_sample

The parameters that say which rows a frame holds are the family's (for flow
tables ``tenants``, ``flows``, ``acquire``: ``families/flow.py``).
"""

from __future__ import annotations

import numpy as np


def pmf(kind: str, n: int, theta: float) -> np.ndarray:
    if kind == "uniform":
        return np.full(n, 1.0 / n)
    if kind == "zipf":  # bounded: rank k drawn in proportion to k^-theta
        p = np.arange(1, n + 1, dtype=np.float64) ** -float(theta)
        return p / p.sum()
    raise ValueError(f"unknown distribution {kind!r}")


def apportion(p: np.ndarray, total: int) -> np.ndarray:
    """``total`` split in proportion to ``p`` by largest remainder: the same
    counts for every seed."""
    raw = p * total
    base = np.floor(raw).astype(np.int64)
    short = total - int(base.sum())
    if short:
        base[np.argsort(-(raw - base), kind="stable")[:short]] += 1
    return base


def encode_frames(family, cols, first_xid: int) -> list:
    """The batch frames of ``cols`` (a mix's columns, ``[n_frames,
    frame_rows, ...]`` each), as bytes, with consecutive xids."""
    return [family.encode_batch(first_xid + k, *[c[k] for c in cols])
            for k in range(len(cols[0]))]


def open_schedule(traffic: dict, seconds: float):
    """Due offsets (seconds from the window's start) of every frame of an
    open-loop window. At a constant rate frame k is due at ``k * dt``, never
    "last send + dt". ``phases`` (optional) multiplies the rate by ``factor``
    for ``for_s`` seconds in every ``every_s``, from ``start_s`` on: a flash
    crowd. The same schedule for every seed."""
    rows = float(frame_rows(traffic))
    rate = float(traffic["rate_rows_per_s"])
    phases = traffic.get("phases")
    if not phases:
        dt = rows / rate
        n = max(1, int(np.floor(seconds / dt + 1e-9)))
        return np.arange(n) * dt
    t = np.arange(0.0, seconds + 1e-9, 0.001)
    mult = np.ones_like(t)
    for ph in phases:
        since = t - float(ph.get("start_s", 0.0))
        on = (since >= 0) & (since % float(ph["every_s"]) < float(ph["for_s"]))
        mult = np.where(on, mult * float(ph["factor"]), mult)
    sent = np.concatenate([[0.0], np.cumsum(rate * mult[:-1] * 0.001)])
    n = max(1, int(np.floor(sent[-1] / rows + 1e-9)))
    return np.interp(np.arange(n) * rows, sent, t)


def frame_rows(traffic: dict) -> int:
    return 1 if traffic["msg"] == "single" else int(traffic["frame_rows"])


def reachable_rows(traffic: dict) -> int:
    """The most rows the mix can have in flight at the door."""
    rows = frame_rows(traffic)
    if traffic["loop"] == "open":
        return rows * int(traffic["inflight_window_frames"])
    return rows * int(traffic["connections"]) * int(
        traffic["outstanding"]) * int(traffic["processes"])
