"""Sees a stall of the server process while a window runs, and says where
it was: the harness's own, nothing of the program is changed.

Three watchers, cheap enough to stay on in every run (70 wake-ups a second):

* a ticker thread that sleeps 20 ms at a time and records every gap over
  50 ms: a gap means no Python thread of this process could run (the GIL was
  held through a long C call, a collection of the garbage collector, or the
  whole process was off the CPU). It also re-arms ``faulthandler``'s C
  watchdog, which needs no GIL and dumps every thread's Python stack when
  the ticker itself has been stuck for ``DUMP_AFTER_S``;
* a progress thread that reads a counter of finished dispatches every 50 ms
  and, when it has not moved for ``STALL_S`` although requests are in
  flight, writes every thread's Python stack, its kernel state, wait channel
  and kernel stack (``/proc/self/task``) to the dump file, again every
  half second until the counter moves;
* the garbage collector's callbacks: every collection over 20 ms.

``/proc/stat`` (per-CPU steal), ``/proc/vmstat`` and ``/proc/pressure`` are
read before and after, for what the host did meanwhile.
"""

from __future__ import annotations

import faulthandler
import gc
import os
import sys
import threading
import time
import traceback

TICK_S = 0.02
GAP_S = 0.05
STALL_S = 0.4
DUMP_AFTER_S = 0.6
GC_S = 0.02
_VMSTAT = ("pgmajfault", "pgfault", "allocstall_normal", "allocstall_movable",
           "compact_stall", "thp_fault_alloc", "numa_pages_migrated",
           "pswpin", "pswpout")


def _read(path: str) -> str:
    try:
        with open(path, encoding="ascii", errors="replace") as f:
            return f.read()
    except OSError:
        return ""


def host_counters() -> dict:
    """What the host has done so far, cumulative."""
    out = {"steal_ticks": {}, "vmstat": {}, "pressure": {}}
    for line in _read("/proc/stat").splitlines():
        w = line.split()
        if w and w[0].startswith("cpu") and len(w) > 8:
            out["steal_ticks"][w[0]] = int(w[8])
    for line in _read("/proc/vmstat").splitlines():
        w = line.split()
        if len(w) == 2 and w[0] in _VMSTAT:
            out["vmstat"][w[0]] = int(w[1])
    for what in ("cpu", "memory", "io"):
        for line in _read(f"/proc/pressure/{what}").splitlines():
            w = line.split()
            if w and w[0] == "some":
                out["pressure"][what] = int(w[-1].split("=")[1])  # us stalled
    return out


def host_delta(a: dict, b: dict) -> dict:
    tick = os.sysconf("SC_CLK_TCK")
    steal = {k: (b["steal_ticks"].get(k, 0) - v) / tick
             for k, v in a["steal_ticks"].items()}
    return {
        "steal_s_total": steal.get("cpu", 0.0),
        "steal_s_worst_cpu": max(
            [v for k, v in steal.items() if k != "cpu"] or [0.0]),
        "vmstat": {k: b["vmstat"].get(k, 0) - v
                   for k, v in a["vmstat"].items()
                   if b["vmstat"].get(k, 0) - v},
        "pressure_ms": {k: (b["pressure"].get(k, 0) - v) / 1e3
                        for k, v in a["pressure"].items()},
    }


def _threads_report() -> str:
    """Every thread: Python stack, kernel state, wait channel, kernel stack."""
    names = {t.native_id: t.name for t in threading.enumerate()}
    idents = {t.ident: t for t in threading.enumerate()}
    lines = []
    for ident, frame in sys._current_frames().items():
        t = idents.get(ident)
        lines.append(f"--- python thread {t.name if t else ident} "
                     f"(tid {t.native_id if t else '?'})")
        lines.extend(s.rstrip() for s in traceback.format_stack(frame)[-8:])
    base = "/proc/self/task"
    try:
        tids = sorted(os.listdir(base), key=int)
    except OSError:
        tids = []
    for tid in tids:
        stat = _read(f"{base}/{tid}/stat")
        comm = _read(f"{base}/{tid}/comm").strip()
        try:
            rest = stat.rsplit(")", 1)[1].split()
            state, utime, stime, cpu = rest[0], rest[11], rest[12], rest[36]
        except (IndexError, ValueError):
            continue
        wchan = _read(f"{base}/{tid}/wchan").strip()
        if state == "S" and int(tid) not in names and wchan in (
                "futex_wait_queue", "futex_do_wait", "do_epoll_wait", "ep_poll",
                "0", ""):
            continue  # an idle native thread
        kstack = " < ".join(
            ln.split()[-1] for ln in _read(
                f"{base}/{tid}/stack").splitlines()[:6])
        lines.append(f"tid {tid} {comm} [{names.get(int(tid), '')}] state "
                     f"{state} cpu {cpu} utime {utime} stime {stime} wchan "
                     f"{wchan} kstack {kstack}")
    return "\n".join(lines)


class StallWatch:
    def __init__(self, progress, dump_path: str):
        """``progress()`` returns a number that grows while the server
        works; ``dump_path`` takes the stacks."""
        self.progress = progress
        self.dump_path = dump_path
        self.t0 = 0.0
        self.gaps = []  # (seconds into the window at its start, length)
        self.stalls = []  # (seconds into the window at its start, length)
        self.gcs = []  # (seconds into the window, generation, length)
        self._stop = threading.Event()
        self._threads = []
        self.gap_max = 0.0
        self._gc_at = 0.0
        self._fh = None
        self._host0 = None

    # -- watchers -----------------------------------------------------------
    def _ticker(self) -> None:
        armed = 0.0
        while not self._stop.is_set():
            t = time.monotonic()
            if t - armed > 0.2:
                faulthandler.dump_traceback_later(
                    DUMP_AFTER_S, repeat=False, file=self._fh)
                armed = t
            time.sleep(TICK_S)
            gap = time.monotonic() - t
            self.gap_max = max(self.gap_max, gap)
            if gap > GAP_S:
                self.gaps.append((t - self.t0, gap))
        faulthandler.cancel_dump_traceback_later()

    def _watch_progress(self) -> None:
        last, moved = self.progress(), time.monotonic()
        began, dumped = None, 0.0
        while not self._stop.is_set():
            time.sleep(0.05)
            now, value = time.monotonic(), self.progress()
            if value != last:
                if began is not None:
                    self.stalls.append((began - self.t0, now - began))
                    self._fh.write(f"=== progress again after "
                                   f"{now - began:.3f}s\n")
                    self._fh.flush()
                    began = None
                last, moved = value, now
                continue
            if now - moved > STALL_S and now - dumped > 0.5:
                if began is None:
                    began = moved
                dumped = now
                self._fh.write(
                    f"=== no dispatch finished for {now - moved:.3f}s, "
                    f"{moved - self.t0:.3f}s into the window\n"
                    + _threads_report() + "\n")
                self._fh.flush()
        if began is not None:
            self.stalls.append((began - self.t0, time.monotonic() - began))

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_at = time.monotonic()
        else:
            dur = time.monotonic() - self._gc_at
            if dur > GC_S:
                self.gcs.append((time.monotonic() - self.t0,
                                 info.get("generation"), dur))

    # -- life ---------------------------------------------------------------
    def start(self, t0: float) -> None:
        self.t0 = t0
        self._fh = open(self.dump_path, "a", encoding="utf-8")
        self._host0 = host_counters()
        gc.callbacks.append(self._on_gc)
        for body in (self._ticker, self._watch_progress):
            th = threading.Thread(target=body, daemon=True,
                                  name="cellbench-" + body.__name__.strip("_"))
            th.start()
            self._threads.append(th)

    def stop(self, t_end: float) -> dict:
        """Stops the watchers; reports what fell inside ``[t0, t_end]``."""
        self._stop.set()
        for th in self._threads:
            th.join(timeout=2.0)
        try:
            gc.callbacks.remove(self._on_gc)
        except ValueError:
            pass
        host = host_delta(self._host0, host_counters())
        self._fh.close()
        span = t_end - self.t0

        def inside(rows, length_at=None):
            # what ended after the window began and began before it was over
            return [tuple(round(x, 4) if isinstance(x, float) else x
                          for x in r) for r in rows
                    if r[0] + (r[length_at] if length_at else 0.0) >= 0
                    and r[0] <= span + 5]

        gaps, gcs = inside(self.gaps, 1), inside(self.gcs)
        # the loop's last replies are no stall: only what began in the window
        stalls = [s for s in inside(self.stalls, 1) if s[0] <= span - STALL_S]
        return {
            "gaps": gaps, "stalls": stalls, "gcs": gcs, "host": host,
            "gap_max_s": self.gap_max,
            "stall_max_s": max([s[1] for s in stalls] or [0.0]),
            "gc_max_s": max([g[2] for g in gcs] or [0.0]),
            "gc_total_s": sum(g[2] for g in gcs),
        }
