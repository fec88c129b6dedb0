"""One run of one cell: ``python3 -m cellbench.run --workload <name> --seed
<n> --seconds <s> --trace <0|1>`` from the root of a checkout.

This process holds the chip(s) and is the token server; the load generators
are child processes that never load JAX. Order of a run: start the
generators (they build their frames meanwhile), load the rules and start the
door (``warmup()``), warm up by the cell's own traffic until nothing compiles
any more, let the rule windows drain, measure for ``--seconds``, probe the
verdicts through the same door, print one JSON line. Without an accelerator,
or with fewer chips than the cell asks for, it exits 2 and prints no result.

What a rule, a row and a frame are is the deployment's family's
(``cellbench/families/``); this module keeps the order of a run, the timing,
the void windows and the stall watch, and calls the family where it would
have to know.
"""

from __future__ import annotations

import time

_T_IMPORT = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cellbench import deploy, manifest, stallwatch  # noqa: E402

ROOT = manifest.ROOT
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 3.0  # the slice of the window the profiler records
REPLY_BIN_S = 0.1  # loadgen.BIN_S: the admitted-token ledger's bins
USUAL_REPLY_S = 0.2  # a reply is usually this fast or faster
WARM_SECONDS = 1.5
MAX_WARM_PASSES = 4
FREEZE_S = 0.4  # a gap this long in every process at once: the machine's
MAX_VOID_WINDOWS = 2


def process_start_monotonic() -> float:
    """``time.monotonic()`` at the start of this process, from /proc."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        started_boot = ticks / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started_boot
        if 0 <= age < 3600:
            return time.monotonic() - age
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return _T_IMPORT


def weighted_percentile(values, weights, q: float) -> float:
    order = np.argsort(values, kind="stable")
    v, w = values[order], weights[order]
    cum = np.cumsum(w)
    return float(v[np.searchsorted(cum, q / 100.0 * cum[-1], side="left")])


class Clients:
    """The generator processes of a run."""

    def __init__(self, cell, seed: int, seconds: float, work: str, say):
        self.say = say
        self.procs = []
        self.lost = []
        self.port_file = os.path.join(work, "port")
        for i in range(int(cell.traffic["processes"])):
            plan = {"traffic": cell.traffic, "config_file": cell.config_file,
                    "family_dirs": cell.dirs, "seed": seed, "proc": i,
                    "seconds": seconds,
                    "warm_seconds": float(cell.traffic.get(
                        "warm_seconds", WARM_SECONDS)),
                    "port_file": self.port_file}
            path = os.path.join(work, f"plan{i}.json")
            with open(path, "w", encoding="utf-8") as f:
                json.dump(plan, f)
            env = dict(os.environ, PYTHONPATH=ROOT + os.pathsep
                       + os.environ.get("PYTHONPATH", ""))
            self.procs.append(subprocess.Popen(
                [sys.executable, os.path.join(ROOT, "cellbench", "loadgen.py"),
                 path], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True, env=env, cwd=ROOT))
        self.planned = [0] * len(self.procs)

    def read(self, i: int):
        line = self.procs[i].stdout.readline()
        if not line:
            if i not in self.lost:
                self.lost.append(i)
                self.say(f"generator {i} died "
                         f"(exit {self.procs[i].poll()})")
            return None
        return json.loads(line)

    def expect_all(self, key: str) -> None:
        for i in range(len(self.procs)):
            out = self.read(i)
            if out is None or key not in out:
                raise RuntimeError(f"generator {i} did not get to {key!r}")
            if "planned_rows" in out:
                self.planned[i] = out["planned_rows"]

    def command(self, words: str) -> list:
        for i, p in enumerate(self.procs):
            if i in self.lost:
                continue
            try:
                p.stdin.write(words + "\n")
                p.stdin.flush()
            except OSError:
                self.lost.append(i)
        return [None if i in self.lost else self.read(i)
                for i in range(len(self.procs))]

    def close(self) -> None:
        for p in self.procs:
            try:
                if p.poll() is None:
                    p.stdin.write("quit\n")
                    p.stdin.flush()
            except OSError:
                pass
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


class Witness:
    """A child process that only sleeps, and reports every sleep that
    overran (``loadgen.py witness``): the evidence that a stall was the
    machine's and not the server's."""

    def __init__(self):
        env = dict(os.environ, PYTHONPATH=ROOT + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        self.gaps = []  # (monotonic start, length)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "cellbench", "loadgen.py"),
             "witness"], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, env=env, cwd=ROOT)
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            try:
                gap = json.loads(line)
            except ValueError:
                continue
            if "start" in gap:
                self.gaps.append((float(gap["start"]), float(gap["len"])))

    def alive(self) -> bool:
        return self.proc.poll() is None

    def close(self) -> None:
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            self.proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self.reader.join(timeout=2.0)


def machine_froze(t0: float, server_gaps: list, generator_gaps: list,
                  witness_gaps: list):
    """The longest stretch in which the server process, every generator
    process and the witness process all stood still at once for
    ``FREEZE_S`` or more, as ``(seconds into the window, length)``, or None.
    ``server_gaps`` and each list of ``generator_gaps`` hold ``(start into
    the window, length)``, ``witness_gaps`` ``(monotonic start, length)``.
    No generator or no witness means no evidence, and None."""
    others = [list(g) for g in generator_gaps]
    others.append([(at - t0, ln) for at, ln in witness_gaps])
    if len(others) < 2:
        return None
    worst = None
    for at, ln in server_gaps:
        lo, hi = at, at + ln
        for gaps in others:
            # the part of [lo, hi] this process stood still through as well
            best = None
            for g_at, g_ln in gaps:
                a, b = max(lo, g_at), min(hi, g_at + g_ln)
                if b - a >= FREEZE_S and (best is None
                                          or b - a > best[1] - best[0]):
                    best = (a, b)
            if best is None:
                lo = hi = 0.0
                break
            lo, hi = best
        if hi - lo >= FREEZE_S and (worst is None or hi - lo > worst[1]):
            worst = (lo, hi - lo)
    return worst


def settle(built, dep) -> None:
    """The shed ladder back at NORMAL, then the rule windows empty."""
    deadline = time.monotonic() + 10
    while (built.server.overload.snapshot()["level"] != 0
           and time.monotonic() < deadline):
        time.sleep(0.05)
    time.sleep((dep.window_ms + 2 * dep.bucket_ms) / 1000.0)


def warm_up(built, clients, cell, dep, seed, compiles, say) -> dict:
    """Drive every kind of dispatch the mix can reach before the window:
    what the family drives in process (flow: each reachable fused depth with
    the mix's own acquires), the steady mix, backlog bursts through the door;
    again until a pass compiles nothing."""
    from sentinel_tpu.trace import ring as flight

    parts = {}
    t_w = time.monotonic()
    flight.arm(sample=0.0)  # aggregate stages only: FUSE, DEVICE_IN/OUT
    since = time.monotonic_ns()
    depths = dep.family.drive_before_window(built, cell.traffic, dep, seed,
                                            compiles, say)
    burst_rows = 2 * built.server.fuse_depth * built.server.max_batch
    passes = 0
    while True:
        passes += 1
        n0 = len(compiles)
        clients.command("warm")
        clients.command(f"burst {burst_rows}")
        fresh = compiles[n0:]
        say(f"warm-up pass {passes}: steady mix "
            f"{cell.traffic.get('warm_seconds', WARM_SECONDS)}s + burst of "
            f"{burst_rows} rows, {len(fresh)} compiles"
            + "".join(f"; {name} {dur:.2f}s" for _t, name, dur in fresh))
        if not fresh or passes >= MAX_WARM_PASSES:
            break
    seen = sorted({e["aux"] for e in flight.events(
        since_ns=since, stages={flight.FUSE})})
    flight.disarm()
    missing = [d for d in depths if d not in seen]
    say(f"warm-up: fused depths reachable {depths}, dispatched {seen}"
        + (f", NOT reached {missing}" if missing else ""))
    settle(built, dep)
    parts["warm_traffic_s"] = time.monotonic() - t_w
    parts["warm_passes"] = passes
    parts["fused_depths_missing"] = missing
    return parts


def server_counters() -> dict:
    from sentinel_tpu.metrics.server import server_metrics

    sm = server_metrics()
    return {"stages": sm.stage_snapshot(), "shed": sm.shed_totals(),
            "fused_frames": sm.fused_frames_total,
            "stage_max_ms": {k: getattr(sm, k).snapshot()["max"] for k in (
                "intake_ms", "dispatch_ms", "decide_ms", "write_ms")},
            "cpu_s": time.process_time(), "t": time.monotonic()}


def trace_slice(work: str, t_from: float, seconds: float, sample: float,
                out: dict) -> None:
    """Record the profiler and the flight recorder over one slice."""
    import jax
    from sentinel_tpu.trace import ring as flight

    prof = os.path.join(work, "profile")
    shutil.rmtree(prof, ignore_errors=True)
    wait = t_from - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    flight.arm(sample=sample)
    jax.profiler.start_trace(prof, profiler_options=opts)
    lo = time.monotonic_ns()
    with jax.profiler.TraceAnnotation("cellbench.sync", t_ns=lo):
        pass
    time.sleep(seconds)
    hi = time.monotonic_ns()
    events = flight.events(since_ns=lo)
    flight.disarm()
    jax.profiler.stop_trace()
    out.update(dir=prof, lo_ns=lo, hi_ns=hi,
               events=[e for e in events if e["t_ns"] <= hi])


def merge_clients(results: list, work: str, clients: Clients) -> dict:
    tot = {"attempted": 0, "failed_rows": 0, "decided": 0,
           "decided_in_window": 0, "never_rows": 0, "duplicates": 0,
           "failed": {}, "status_hist": np.zeros(16, np.int64)}
    lat, w, lag, admitted, lat_max = [], [], [], None, None
    for i, r in enumerate(results):
        if r is None or "attempted" not in r:
            tot["attempted"] += clients.planned[i]
            tot["failed_rows"] += clients.planned[i]
            tot["failed"]["lost_client"] = (
                tot["failed"].get("lost_client", 0) + clients.planned[i])
            continue
        for k in ("attempted", "failed_rows", "decided", "decided_in_window",
                  "never_rows", "duplicates"):
            tot[k] += r[k]
        for k, v in r["failed"].items():
            tot["failed"][k] = tot["failed"].get(k, 0) + v
        tot["status_hist"] += np.asarray(r["status_hist"])
        with np.load(os.path.join(work, f"result{i}.npz")) as z:
            lat.append(z["lat_s"])
            w.append(z["lat_w"])
            lag.append(z["lag_s"])
            admitted = z["admitted"] if admitted is None else (
                admitted + z["admitted"])
            lat_max = z["lat_max"] if lat_max is None else np.maximum(
                lat_max, z["lat_max"])
    tot["lat_s"] = np.concatenate(lat) if lat else np.empty(0)
    tot["lat_w"] = np.concatenate(w) if w else np.empty(0, np.int64)
    tot["lag_s"] = np.concatenate(lag) if lag else np.empty(0)
    tot["admitted"] = admitted
    tot["lat_max"] = lat_max
    return tot


def window_invariants(client: dict, dep, say, compared=None) -> bool:
    """The guarantees, held against the window's own replies. ``compared``
    (a dict) takes every number compared as ``name: [number, limit]``."""
    ok = True
    if compared is None:
        compared = {}
    checks = dep.window_checks(client) + [
        ("rows answered twice", client["duplicates"], 0)]
    legal = np.zeros(16, bool)
    legal[[deploy.OK, deploy.BLOCKED, deploy.SHOULD_WAIT, deploy.NO_RULE,
           deploy.TOO_MANY, deploy.FAIL, deploy.OVERLOAD, deploy.STANDBY,
           deploy.MOVED, deploy.DEGRADED]] = True
    checks.append(("rows with an unknown status",
                   int(client["status_hist"][~legal].sum()), 0))
    for what, got, limit in checks:
        say(f"invariant {what}: {got} (limit {limit})")
        compared[what.replace(" ", "_")] = [int(got), limit]
        ok &= got <= limit
    adm = client["admitted"]
    if adm is not None and adm.size:
        # Tokens admitted under a ledger key (a metered flow), by the time
        # their reply came. Every window of the server spans one bucket less
        # than ``window_ms`` up to ``window_ms`` of decisions (900..1000 ms
        # with ten buckets of 100), and a reply comes at most its latency
        # after its decision: a span of replies whose slowest took L holds
        # decisions of the span + L, which ceil((span + L) / shortest window)
        # windows cover. The span is the shortest window less a usual reply
        # (700 ms there): with L under 200 ms, the usual case, that is one
        # window and the limit is the count.
        shortest = (dep.window_ms - dep.bucket_ms) / 1000.0
        k = max(1, int(round((shortest - USUAL_REPLY_S) / REPLY_BIN_S)))
        c = np.cumsum(np.pad(adm, ((0, 0), (k, 0))), axis=1)
        most = c[:, k:] - c[:, :-k]  # [keys, spans ending at each bin]
        lm = np.pad(client["lat_max"], (k - 1, 0))
        slowest = np.max(np.stack([lm[i:i + adm.shape[1]]
                                   for i in range(k)]), axis=0)
        windows = np.ceil((k * REPLY_BIN_S + slowest) / shortest - 1e-9)
        allowed = (dep.ledger_counts()[:, None]
                   * dep.window_ms / 1000.0 * windows[None, :])
        ratio = (most / allowed).max(axis=1)
        worst = int(np.argmax(ratio))
        say(f"invariant admitted tokens per window over count, worst of "
            f"{int((most.max(axis=1) > 0).sum())} metered flows: "
            f"{ratio[worst]:.6f} (limit 1; slowest reply "
            f"{client['lat_max'].max() * 1e3:.1f} ms)")
        compared["admitted_over_count"] = [float(ratio[worst]), 1]
        ok &= bool(ratio[worst] <= 1.0)
    return ok


def window(clients, cell, seconds: float, trace: int, work: str,
           compiles: list, say, progress):
    """One measured window: counters before, the generators' window (with
    the traced slice inside it, and the stall watch over all of it), counters
    after. ``progress`` is the family's count of finished work, which the
    stall watch expects to keep rising."""
    c0 = server_counters()
    t0 = time.monotonic() + 0.25
    watch = stallwatch.StallWatch(progress, os.path.join(work, "stalls.txt"))
    watch.start(t0)
    sliced = {}
    tracer = None
    if trace:
        tracer = threading.Thread(target=trace_slice, args=(
            work, t0 + min(2.0, seconds / 4.0),
            min(TRACE_SECONDS, seconds / 2.0),
            float(cell.traffic.get("trace_sample", 1.0)), sliced))
        tracer.start()
    for i, p in enumerate(clients.procs):
        p.stdin.write(f"measure {t0!r} {seconds!r} "
                      f"{os.path.join(work, f'result{i}.npz')}\n")
        p.stdin.flush()
    results = [clients.read(i) for i in range(len(clients.procs))]
    stalls = watch.stop(t0 + seconds)
    c1 = server_counters()
    if tracer is not None:
        tracer.join()
    stalls["generator_gaps"] = [
        [(round(at, 3), round(ln, 4)) for at, ln in r["tick_gaps"]]
        for r in results if r and "tick_gaps" in r]
    report_stalls(stalls, c0, c1, os.path.join(work, "stalls.txt"), say)
    sliced["stalls"] = stalls
    in_window = [c for c in compiles if t0 <= c[0] <= t0 + seconds + 1]
    for at, name, dur in in_window:
        say(f"COMPILED INSIDE THE WINDOW: {name}, {dur:.3f}s, ending "
            f"{at - t0:.3f}s after its start")
    return t0, c0, c1, sliced, in_window, results


def report_stalls(stalls: dict, c0: dict, c1: dict, dump: str, say) -> None:
    """One line on what the stall watch saw, and the stacks if it saw a
    stall: a run that lost rows to a stall names where the server stood."""
    grew = {k: v for k, v in c1["stage_max_ms"].items()
            if v is not None and v > (c0["stage_max_ms"].get(k) or 0.0)}
    host = stalls["host"]
    say(f"stall watch: longest gap of the ticker "
        f"{stalls['gap_max_s'] * 1e3:.1f} ms, longest time with no dispatch "
        f"finished {stalls['stall_max_s'] * 1e3:.1f} ms, collector "
        f"{stalls['gc_total_s'] * 1e3:.1f} ms in {len(stalls['gcs'])} "
        f"collections over 20 ms (longest {stalls['gc_max_s'] * 1e3:.1f}); "
        f"steal {host['steal_s_total']:.2f}s (worst CPU "
        f"{host['steal_s_worst_cpu']:.2f}s), pressure ms "
        f"{host['pressure_ms']}, vmstat {host['vmstat']}; generators' "
        f"ticker gaps over 100 ms (seconds into the window, length) "
        f"{stalls['generator_gaps']}; stage maxima that "
        f"grew in the window (ms): {grew}")
    if stalls["stalls"] or stalls["gap_max_s"] > stallwatch.DUMP_AFTER_S:
        say(f"STALL in the window: gaps {stalls['gaps']}, no progress "
            f"{stalls['stalls']} (seconds into the window, length), "
            f"collections {stalls['gcs']}")
        try:
            with open(dump, encoding="utf-8", errors="replace") as f:
                for line in f.read().splitlines()[:400]:
                    say("  | " + line)
        except OSError:
            pass


def e2e_metrics(client: dict, closed: bool, seconds: float) -> dict:
    decided = client["decided_in_window"] if closed else client["decided"]
    out = {"decided": decided, "decided_verdicts_per_s": decided / seconds}
    if client["lat_s"].size:
        ms = client["lat_s"] * 1e3
        out["verdict_latency_p50_ms"] = weighted_percentile(
            ms, client["lat_w"], 50)
        out["verdict_latency_p95_ms"] = weighted_percentile(
            ms, client["lat_w"], 95)
    return out


def start_jax(cell, require_chip: bool, out=None):
    """Devices, compile cache and the compile listener. Returns ``(devices,
    say, compiles)``; raises SystemExit(2) without the chips."""
    import jax

    if out is None:
        def out(line: str) -> None:
            print(line, flush=True)

    devices = jax.devices()
    kind = devices[0].device_kind
    tag = f"[{cell.name} on {kind} x{len(devices)}]"

    def say(msg: str) -> None:
        out(f"{tag} {msg}")

    if require_chip and devices[0].platform != "tpu":
        say(f"needs a TPU, JAX found {devices}: no result")
        raise SystemExit(2)
    if len(devices) < cell.chips:
        say(f"needs {cell.chips} chips, JAX found {len(devices)}: no result")
        raise SystemExit(2)

    from sentinel_tpu.core.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = []  # (monotonic at end, jit name, seconds)
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, dur, **kw: compiles.append(
            (time.monotonic(), str(kw.get("fun_name", "?")), float(dur)))
        if event == COMPILE_EVENT else None)
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache {cache_dir}: {n_cached} entries")
    return devices[:cell.chips], say, compiles


def work_dir(name: str) -> str:
    work = os.path.join(ROOT, ".cellbench_run", name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    return work


def connect(clients, built) -> None:
    clients.expect_all("built")
    with open(clients.port_file + ".tmp", "w", encoding="utf-8") as f:
        f.write(str(built.server.port))
    os.replace(clients.port_file + ".tmp", clients.port_file)
    clients.expect_all("connected")


def run_cell(manifest_path: str, workload: str, seed: int, seconds: float,
             trace: int, require_chip: bool = True, wrap_service=None,
             out=None) -> dict:
    """Everything a run does once the arguments are known. Returns the
    result object; raises SystemExit(2) without the chips."""
    t_proc = process_start_monotonic()
    cell = manifest.Cell(manifest_path, workload)
    dep = deploy.load(cell.config_file, cell.dirs)

    devices, say, compiles = start_jax(cell, require_chip, out)
    kind = devices[0].device_kind
    say(f"seed {seed}, {seconds}s, trace {trace}")
    work = work_dir(workload)
    parts = {"import_and_devices_s": time.monotonic() - t_proc}
    clients = Clients(cell, seed, seconds, work, say)
    witness = Witness()
    built = None
    voided = []
    try:
        from cellbench import server as sut

        built = sut.build(dep, devices, say, wrap_service=wrap_service)
        parts.update(built.parts)
        t = time.monotonic()
        connect(clients, built)
        parts["clients_s"] = time.monotonic() - t
        parts.update(warm_up(built, clients, cell, dep, seed, compiles, say))
        say(f"set-up compiles: {len(compiles)} programs, "
            f"{sum(c[2] for c in compiles):.1f}s in the compiler or its cache")

        # -- the measured window --------------------------------------------
        # A window through which the whole machine stood still (this
        # process, every generator and the witness, all at once for
        # FREEZE_S or more) says nothing of the server: it is printed,
        # counted and measured again, at most MAX_VOID_WINDOWS times.
        while True:
            t0, c0, c1, sliced, in_window, results = window(
                clients, cell, seconds, trace, work, compiles, say,
                dep.family.progress(built))
            client = merge_clients(results, work, clients)
            froze = machine_froze(
                t0, sliced["stalls"]["gaps"],
                sliced["stalls"]["generator_gaps"], witness.gaps)
            if (froze is None or clients.lost or not witness.alive()
                    or len(voided) >= MAX_VOID_WINDOWS):
                break
            was = e2e_metrics(client, cell.traffic["loop"] == "closed",
                              seconds)
            voided.append({"at_s": froze[0], "length_s": froze[1],
                           "attempted": int(client["attempted"]),
                           "failed": int(client["failed_rows"])})
            say(f"WINDOW {len(voided)} VOID: the machine stood still for "
                f"{froze[1]:.3f}s from {froze[0]:.3f}s into it (server, "
                f"{len(sliced['stalls']['generator_gaps'])} generator(s) "
                f"and the witness process at once); it read attempted "
                f"{client['attempted']}, failed {client['failed_rows']} "
                f"{client['failed']}, " + ", ".join(
                    f"{k} {v:.6g}" for k, v in was.items())
                + "; measuring again")
            settle(built, dep)
        setup_s = t0 - t_proc

        # -- correct ---------------------------------------------------------
        from cellbench import probe

        probed = probe.Probe(built.server.port, dep, cell.traffic, seed,
                             say=say).run()
        compared = {"probe_" + c["check"]: [c.get("mismatches", c.get(
            "error")), c.get("limit", 0)] for c in probed["checks"]}
        sound = window_invariants(client, dep, say, compared)
        compared["generators_lost"] = [len(clients.lost), 0]
        correct = bool(probed["ok"] and sound and not clients.lost)
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
    finally:
        clients.close()
        witness.close()
        if built is not None:
            built.close()

    e2e = e2e_metrics(client, cell.traffic["loop"] == "closed", seconds)
    decided = e2e.pop("decided")
    e2e["setup_s"] = setup_s
    hist = client["status_hist"]
    if voided:
        say(f"{len(voided)} void window(s) before this one: {voided}; "
            f"set-up runs to the start of the window reported")
    say(f"set-up {setup_s:.2f}s by part: " + ", ".join(
        f"{k} {v:.2f}" if isinstance(v, float) else f"{k} {v}"
        for k, v in parts.items()))
    say(f"window {seconds}s: attempted {client['attempted']}, failed "
        f"{client['failed_rows']} {client['failed']}, decided {decided}; "
        f"status OK {hist[0]} BLOCKED {hist[1]} SHOULD_WAIT {hist[2]} "
        f"NO_RULE {hist[3]} TOO_MANY {hist[4]} OVERLOAD {hist[8]} "
        f"DEGRADED {hist[12]}; send lag max "
        f"{(client['lag_s'].max() * 1e3 if client['lag_s'].size else 0):.2f} ms; "
        f"server shed "
        f"{ {k: v - c0['shed'].get(k, 0) for k, v in c1['shed'].items()} }")
    say("end to end: " + ", ".join(f"{k} {v:.6g}" for k, v in e2e.items()))

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": int(client["attempted"]),
              "failed": int(client["failed_rows"]), "metrics": {},
              "device": device, "void_windows": len(voided)}
    units = {m["name"]: m["unit"] for m in cell.manifest["end_to_end"]}
    if not trace:
        for m in cell.end_to_end():
            if m["name"] in e2e:
                result["metrics"][m["name"]] = {
                    "value": e2e[m["name"]], "unit": units[m["name"]]}
        result["compared"] = compared
        return result

    # -- the traced run: per-layer metrics -----------------------------------
    from cellbench import trace as tr

    t = tr.Trace(tr.find_xplane(sliced["dir"]))
    flow = [e for e in sliced["events"] if e["stage"] in (
        "rx", "client_in", "enqueue", "permit", "prep", "locked", "dispatch",
        "device_in", "reply_taken", "ready", "fetched", "reply_out",
        "account", "device_out")]
    reduced = tr.reduce(
        t, sliced["lo_ns"], sliced["hi_ns"],
        np.asarray([e["t_ns"] for e in flow], np.float64),
        [e["stage"] for e in flow])
    snap = {
        "cell": cell.cell, "traffic": cell.traffic, "config": dep.spec,
        "peaks": deploy.load_json(cell.peaks_file), "device_kind": kind,
        "chips": len(devices), "seconds": seconds, "client": client,
        "decided": decided, "before": c0, "after": c1,
        "compiles_in_window": in_window, "events": sliced["events"],
        "stalls": sliced["stalls"], "voided": voided,
        "slice_s": (sliced["hi_ns"] - sliced["lo_ns"]) / 1e9,
        "trace": reduced, "memory_peak_bytes": peak,
    }
    readers = cell.readers()
    for m in cell.per_layer():
        reader = readers.get(m["name"])
        if reader is None:
            say(f"per-layer {m['name']}: no reader file, left out")
            continue
        value = reader.reduce(snap)
        if value is None:
            say(f"per-layer {m['name']}: nothing to read, left out")
            continue
        result["metrics"][m["name"]] = {"value": float(value),
                                        "unit": m["unit"]}
        say(f"per-layer {m['name']}: {float(value):.6g} {m['unit']}")
    device["busy_s"] = reduced["busy_s_mean"]
    device["window_s"] = reduced["window_s"]
    result["breakdown"] = {"device_ops": reduced["device_ops"],
                           "idle_gaps": reduced["idle_gaps"]}
    say("programs on the median chip: " + json.dumps(reduced["modules"]))
    result["compared"] = compared
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    result = run_cell(args.manifest, args.workload, args.seed, args.seconds,
                      args.trace)
    # every number compared, beside its limit: the last lines of standard
    # error, and the last key of the result line
    for name, (got, limit) in result["compared"].items():
        print(f"compared {name}: {got} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
