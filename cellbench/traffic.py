"""The one general traffic generator. A mix is a data file of parameters
(``cellbench/traffic/<mix>.json``); nothing here knows a mix by name.

Parameters of a mix:

    loop        "open" (absolute schedule at ``rate_rows_per_s``) or "closed"
                (``connections`` x ``outstanding`` frames kept in flight)
    msg         "batch" (BATCH_FLOW frames of ``frame_rows`` rows) or
                "single" (one-token FLOW frames)
    tenants     {"popularity": "zipf"|"uniform", "theta": t}: the namespace a
                frame's rows belong to (one tenant's sidecar sends a frame)
    flows       {"dist": "zipf"|"uniform", "theta": t}: a row's flow by its
                popularity rank inside the tenant
    acquire     {"values": [...], "weights": [...]}: tokens a row asks for
    processes, connections, inflight_window_frames, timeout_ms, trace_sample

Every seed gives the same multiset of frame sizes, arrival times and
tenant frames; the seed permutes which tenant sends when and draws the flows
and the acquires.
"""

from __future__ import annotations

import numpy as np

from cellbench import wire


def _pmf(kind: str, n: int, theta: float) -> np.ndarray:
    if kind == "uniform":
        return np.full(n, 1.0 / n)
    if kind == "zipf":  # bounded: rank k drawn in proportion to k^-theta
        p = np.arange(1, n + 1, dtype=np.float64) ** -float(theta)
        return p / p.sum()
    raise ValueError(f"unknown distribution {kind!r}")


def _apportion(p: np.ndarray, total: int) -> np.ndarray:
    """``total`` split in proportion to ``p`` by largest remainder: the same
    counts for every seed."""
    raw = p * total
    base = np.floor(raw).astype(np.int64)
    short = total - int(base.sum())
    if short:
        base[np.argsort(-(raw - base), kind="stable")[:short]] += 1
    return base


class Mix:
    """Draws frames of one traffic mix over one deployment."""

    def __init__(self, traffic: dict, deployment, seed: int, salt: int):
        self.t = traffic
        self.d = deployment
        self.rng = np.random.default_rng([int(seed), int(salt)])
        self.frame_rows = 1 if traffic["msg"] == "single" else int(
            traffic["frame_rows"])
        self.tenants = np.asarray(deployment.traffic_namespaces(), np.int64)
        tp = traffic["tenants"]
        self.tenant_p = _pmf(tp["popularity"], len(self.tenants),
                             tp.get("theta", 0.0))
        fl = traffic["flows"]
        self.flow_cdf = np.cumsum(_pmf(
            fl["dist"], deployment.flows_per_namespace(),
            fl.get("theta", 0.0)))
        acq = traffic["acquire"]
        self.acq_values = np.asarray(acq["values"], np.int32)
        w = np.asarray(acq["weights"], np.float64)
        self.acq_cdf = np.cumsum(w / w.sum())
        self.uniform_acquire = len(self.acq_values) == 1

    def frame_tenants(self, n_frames: int) -> np.ndarray:
        counts = _apportion(self.tenant_p, n_frames)
        who = np.repeat(self.tenants, counts)
        self.rng.shuffle(who)
        return who

    def rows(self, frame_tenants: np.ndarray):
        """``(flow_ids, acquires)`` as ``[n_frames, frame_rows]`` arrays."""
        shape = (len(frame_tenants), self.frame_rows)
        rank = np.searchsorted(self.flow_cdf, self.rng.random(shape))
        rank = np.minimum(rank, len(self.flow_cdf) - 1)
        ids = self.d.flow_id(frame_tenants[:, None], rank)
        if self.uniform_acquire:
            acq = np.full(shape, self.acq_values[0], np.int32)
        else:
            at = np.searchsorted(self.acq_cdf, self.rng.random(shape))
            acq = self.acq_values[np.minimum(at, len(self.acq_values) - 1)]
        return ids, acq

    def frames(self, n_frames: int):
        return self.rows(self.frame_tenants(n_frames))


def encode_frames(ids: np.ndarray, acq: np.ndarray, first_xid: int) -> list:
    return [wire.encode_batch(first_xid + k, ids[k], acq[k])
            for k in range(len(ids))]


def open_schedule(traffic: dict, seconds: float):
    """Due offsets (seconds from the window's start) of every frame of an
    open-loop window. At a constant rate frame k is due at ``k * dt``, never
    "last send + dt". ``phases`` (optional) multiplies the rate by ``factor``
    for ``for_s`` seconds in every ``every_s``, from ``start_s`` on: a flash
    crowd. The same schedule for every seed."""
    rows = float(traffic["frame_rows"])
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


def reachable_rows(traffic: dict) -> int:
    """The most rows the mix can have in flight at the door."""
    rows = 1 if traffic["msg"] == "single" else int(traffic["frame_rows"])
    if traffic["loop"] == "open":
        return rows * int(traffic["inflight_window_frames"])
    return rows * int(traffic["connections"]) * int(
        traffic["outstanding"]) * int(traffic["processes"])
