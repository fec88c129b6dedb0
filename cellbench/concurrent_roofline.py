"""Analytic bytes and operations of the concurrency step
(``sentinel_tpu/engine/concurrent.py``, ``jit_concurrent_step_b<bucket>``),
and the least time a chip could take for the work of a window. Peaks come
from ``peaks.json`` by ``device_kind``.

Counted is only what any implementation of the mechanism must move, from
the program's own counters (``concurrent_*_total``), so that the share reads
the same work whatever implements the step and cannot pass 100 %. Every cell
is an ``int32``; a token slot is four of them (flow, count, expiry,
generation):

    release row      its token slot read
    ... that freed   the slot written, the flow's gauge cell read and written
    acquire row      a gauge cell and a level cell read
    admitted row     a gauge cell and a token slot written
    expired token    as a release that freed

The packed argument, the verdicts, the expiry scan's block and the prefix
sums are how *this* step does it and are left out: they are what the share
is small by. Operations are a few tens a row; the step is bound by memory.
"""

from __future__ import annotations

CONCURRENT_LANE = 2  # sentinel_tpu.trace.ring.CONCURRENT_LANE
_I32 = 4
_SLOT = 4 * _I32
_OPS_PER_ROW = 40.0
COUNTERS = ("concurrent_dispatch_total", "concurrent_acquire_rows_total",
            "concurrent_release_rows_total", "concurrent_blocked_total",
            "concurrent_already_release_total", "concurrent_expired_total",
            "concurrent_table_full_total")


def window_counts(snap):
    """The lane's counters over the window, or None where the program has
    none (a tree from before PR 41) or made no concurrency dispatch."""
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    if any(k not in a or k not in b for k in COUNTERS):
        return None
    moved = {k: b[k] - a[k] for k in COUNTERS}
    if moved["concurrent_dispatch_total"] <= 0:
        return None
    return moved


def window_model(moved: dict) -> dict:
    """Bytes and operations of the work the counters say was done."""
    acquires = moved["concurrent_acquire_rows_total"]
    releases = moved["concurrent_release_rows_total"]
    freed = releases - moved["concurrent_already_release_total"]
    expired = moved["concurrent_expired_total"]
    # NO_RULE and FAIL rows are not told apart from admitted ones by a
    # counter of their own; the cell has none of either
    admitted = max(0, acquires - moved["concurrent_blocked_total"]
                   - moved["concurrent_table_full_total"])
    cells = (releases * _SLOT + (freed + expired) * (_SLOT + 2 * _I32)
             + expired * _SLOT + acquires * 2 * _I32
             + admitted * (_I32 + _SLOT))
    return {"bytes": float(cells),
            "flops": _OPS_PER_ROW * (acquires + releases + expired)}


def least_seconds(moved: dict, peaks: dict) -> float:
    m = window_model(moved)
    f32_peak = peaks["bf16_flops_per_s"] / peaks["f32_highest_passes"]
    return max(m["flops"] / f32_peak, m["bytes"] / peaks["hbm_bytes_per_s"])


def concurrent_dispatches(snap) -> int:
    """Concurrency dispatches of the traced slice: the DEVICE_IN events
    they mark (``shard`` 2)."""
    return sum(1 for e in snap["events"] if e["stage"] == "device_in"
               and e.get("shard") == CONCURRENT_LANE)


def concurrent_program_seconds(snap) -> float:
    """Device time of the ``jit_concurrent_step*`` programs of the slice."""
    return sum(s for name, s in snap["trace"]["modules"]
               if str(name).startswith("jit_concurrent_step"))
