"""From a JAX profiler trace (``.xplane.pb``) to device busy time, idle gaps
and per-program time. Read with ``jax.profiler.ProfileData`` alone.

What the trace of a TPU holds (looked at by hand, PR 23): one plane per chip
named ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed
HLO operation and whose line ``XLA Modules`` has one event per executed
program; and the plane ``/host:CPU`` with one line per host thread. All
lines share one clock, nanoseconds from the start of the trace. The harness
drops a ``cellbench.sync`` annotation carrying ``time.monotonic_ns()`` on the
host line, which ties that clock to the flight recorder's.
"""

from __future__ import annotations

import glob
import os

import numpy as np

SYNC = "cellbench.sync"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(profile_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return paths[-1]


def _events(line):
    names, start, dur = [], [], []
    for e in line.events:
        names.append(e.name)
        start.append(e.start_ns)
        dur.append(e.duration_ns)
    return (np.asarray(names, object), np.asarray(start, np.float64),
            np.asarray(dur, np.float64))


class Trace:
    """``devices``: ``{plane name: {"ops": (names, start, dur), "modules":
    (names, start, dur)}}`` in trace nanoseconds; ``offset_ns``: what to add
    to a trace time to get ``time.monotonic_ns()`` (None without a sync)."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        self.path = path
        self.devices = {}
        self.offset_ns = None
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/device:") and "TPU" in plane.name:
                lines = {ln.name: ln for ln in plane.lines}
                if OPS_LINE not in lines or MODULES_LINE not in lines:
                    raise ValueError(
                        f"{plane.name} of {path} lacks {OPS_LINE!r} or "
                        f"{MODULES_LINE!r}; it has {sorted(lines)}")
                self.devices[plane.name] = {
                    "ops": _events(lines[OPS_LINE]),
                    "modules": _events(lines[MODULES_LINE])}
            elif plane.name.startswith("/host:CPU"):
                for ln in plane.lines:
                    for e in ln.events:
                        if e.name == SYNC:
                            stats = dict(e.stats)
                            if "t_ns" in stats:
                                self.offset_ns = (float(stats["t_ns"])
                                                  - float(e.start_ns))

    def to_trace_ns(self, monotonic_ns: float) -> float:
        if self.offset_ns is None:
            raise ValueError("the trace holds no cellbench.sync annotation")
        return monotonic_ns - self.offset_ns


def merge(start: np.ndarray, dur: np.ndarray, lo: float, hi: float):
    """The union of the intervals, clipped to ``[lo, hi]``, as two sorted
    arrays of disjoint ``(start, end)``."""
    s = np.maximum(start, lo)
    e = np.minimum(start + dur, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return np.empty(0), np.empty(0)
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > run_end[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], run_end[last]


def busy_and_gaps(start, dur, lo: float, hi: float):
    """``(busy_ns, gaps)``: time covered by at least one interval inside
    ``[lo, hi]``, and the idle gaps as ``(start, length)`` arrays, the
    stretches before the first and after the last interval included."""
    s, e = merge(start, dur, lo, hi)
    busy = float((e - s).sum())
    edges_s = np.append(lo, e)
    edges_e = np.append(s, hi)
    glen = edges_e - edges_s
    keep = glen > 0
    return busy, (edges_s[keep], glen[keep])


def short_name(name: str) -> str:
    """An HLO operation's own name: the trace gives its whole text,
    ``%while.11 = (u32[], ...) while(...)``; keep ``while.11``."""
    return str(name).split(" = ", 1)[0].lstrip("%")[:80]


def top_by_time(names, start, dur, lo: float, hi: float, n: int = 10):
    """The ``n`` names with most time inside the window: ``[[name, seconds]]``."""
    inside = (start >= lo) & (start + dur <= hi)
    total = {}
    for name, d in zip(names[inside], dur[inside]):
        name = short_name(name)
        total[name] = total.get(name, 0.0) + d
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[str(k), v / 1e9] for k, v in top]


def name_gaps(gap_start, gap_len, stage_t_ns, stage_names, n: int = 10):
    """The ``n`` longest gaps, each named by the last flight-recorder stage
    before it began: ``[["after_<stage>", seconds]]``; ``no_request`` when no
    frame was anywhere in the pipeline: nothing was recorded yet, or the
    last thing recorded before the gap belongs to a dispatch whose reply
    had gone out (``reply_out``, or the account half that follows it on the
    native lane: ``account``, then ``device_out``) and nothing more was
    recorded until the gap's end."""
    order = np.argsort(-gap_len)[:n]
    out = []
    for i in order:
        at = np.searchsorted(stage_t_ns, gap_start[i], side="right") - 1
        label = ("no_request" if at < 0 or _replied(stage_names, at)
                 and _quiet(stage_t_ns, at, gap_start[i] + gap_len[i])
                 else "after_" + stage_names[at])
        out.append([label, float(gap_len[i]) / 1e9])
    return out


# what a reply lane records after a dispatch's reply has left (PR 35)
_AFTER_REPLY = ("account", "device_out")


def _replied(stage_names, at: int) -> bool:
    """True when event ``at`` is a ``reply_out``, or the counting that
    follows one: ``account`` / ``device_out`` with a ``reply_out`` right
    before them."""
    while at > 0 and stage_names[at] in _AFTER_REPLY:
        at -= 1
    return stage_names[at] == "reply_out"


def _quiet(stage_t_ns, at: int, gap_end: float) -> bool:
    """True when nothing was recorded from event ``at`` to the gap's end: the
    last reply had gone out and no request had come in."""
    return at + 1 >= len(stage_t_ns) or stage_t_ns[at + 1] >= gap_end


def reduce(trace: Trace, lo_mono_ns: float, hi_mono_ns: float,
           stage_t_ns=None, stage_names=None) -> dict:
    """Everything the per-layer readers take from the trace, for the window
    ``[lo, hi]`` given on the monotonic clock."""
    lo, hi = trace.to_trace_ns(lo_mono_ns), trace.to_trace_ns(hi_mono_ns)
    per_chip = []
    for name in sorted(trace.devices):
        d = trace.devices[name]
        names, start, dur = d["ops"]
        busy, gaps = busy_and_gaps(start, dur, lo, hi)
        mn, ms, md = d["modules"]
        inside = (ms >= lo) & (ms + md <= hi)
        per_chip.append({
            "plane": name, "busy_s": busy / 1e9, "gaps": gaps,
            "ops": (names, start, dur),
            "module_s": float(md[inside].sum()) / 1e9,
            "module_runs": int(inside.sum()),
            "modules": top_by_time(mn, ms, md, lo, hi, 10),
        })
    if not per_chip:
        raise ValueError(f"{trace.path} holds no TPU device plane")
    window_s = (hi - lo) / 1e9
    busy = [c["busy_s"] for c in per_chip]
    median = per_chip[int(np.argsort(busy)[len(busy) // 2])]
    names, start, dur = median["ops"]
    gaps = []
    if stage_t_ns is not None and len(stage_t_ns):
        shift = np.asarray(stage_t_ns, np.float64) - trace.offset_ns
        gaps = name_gaps(median["gaps"][0], median["gaps"][1], shift,
                         list(stage_names))
    return {
        "window_s": window_s,
        "busy_s_mean": float(np.mean(busy)),
        "busy_s_median_chip": median["busy_s"],
        "busy_s_per_chip": busy,
        "module_s_median_chip": median["module_s"],
        "module_runs_median_chip": median["module_runs"],
        "modules": median["modules"],
        "device_ops": top_by_time(names, start, dur, lo, hi, 10),
        "idle_gaps": gaps,
    }
