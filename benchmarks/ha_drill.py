"""Kill-the-primary fault-injection drill (CI smoke + runbook rehearsal).

Two real token servers run as subprocesses on ephemeral ports; a
``FailoverTokenClient`` drives load against the ordered pair. The drill
then:

1. **SIGKILLs the primary mid-load** and measures convergence: the wall
   time from the kill until a request is served by the standby. Must land
   inside the configured failover deadline (``--deadline-ms``, default the
   subsystem's 500ms).
2. **SIGKILLs the standby too** and asserts every subsequent request still
   resolves — pass/block/throttle via the per-rule local fallback policy,
   never an unhandled exception — recording the fallback window's
   blocked-rate.

Subprocess servers (same pattern as ``native/fuzz_frontdoor.py``'s
standalone mode) make the kill honest: no in-process shutdown hooks soften
it. Importable (``run_drill``) so the serve bench and the pytest smoke can
reuse the in-process variant. Exit code is nonzero on any violated
invariant, so CI can gate on it directly::

    JAX_PLATFORMS=cpu python benchmarks/ha_drill.py
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

DRILL_FLOW = 42
WARM_FLOW = 7
N_FALLBACK_PROBES = 400


def _serve_forever(args) -> None:
    """Child mode: one token server on an ephemeral port, announced as a
    JSON line on stdout; runs until killed (that's the point). The
    replication drill reuses this child with role flags (``--standby-of``
    / ``--replicate-to``) and a finite ``--count`` so over-admission is
    measurable."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
    from sentinel_tpu.engine.rules import ThresholdMode

    svc = DefaultTokenService(
        EngineConfig(
            max_flows=64, max_namespaces=4, batch_size=64,
            bucket_ms=args.bucket_ms,
        ),
        lease_ttl_ms=int(args.lease_ttl_ms),
    )
    # WARM_FLOW carries an effectively-unbounded rule so drills can warm
    # jit-compiled paths (decide, lease grant) without touching the finite
    # DRILL_FLOW window the over-admission gates measure
    svc.load_rules(
        [ClusterFlowRule(DRILL_FLOW, args.count, ThresholdMode.GLOBAL),
         ClusterFlowRule(WARM_FLOW, 1e9, ThresholdMode.GLOBAL)]
    )
    server = TokenServer(
        svc, port=0, metrics_port=0,
        standby_of=args.standby_of,
        promote_after_ms=args.promote_after_ms,
        replicate_to=(
            [args.replicate_to] if args.replicate_to else None
        ),
        repl_interval_ms=args.repl_interval_ms,
    )
    server.start()
    print(
        json.dumps({"port": server.port, "metrics_port": server.metrics_port}),
        flush=True,
    )
    while True:
        time.sleep(3600)


def _spawn_server(timeout_s: float = 120.0, extra=None) -> tuple:
    """Start one server child; returns (Popen, port, metrics_port)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # a drill child never holds the chip
    log_dir = os.environ.get("SENTINEL_DRILL_CHILD_LOGS")
    if log_dir:
        stderr = open(
            os.path.join(log_dir, f"child-{time.monotonic_ns()}.err"), "w"
        )
    else:
        stderr = subprocess.DEVNULL
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--serve"]
        + list(extra or ()),
        stdout=subprocess.PIPE, stderr=stderr, text=True,
        env=env,
    )
    deadline = time.monotonic() + timeout_s
    line = ""
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if line.startswith("{"):
            doc = json.loads(line)
            return proc, doc["port"], doc.get("metrics_port")
        if proc.poll() is not None:
            break
    proc.kill()
    raise RuntimeError(f"server child never became ready (last: {line!r})")


def _scrape(metrics_port: int) -> str:
    import urllib.request

    with urllib.request.urlopen(
        f"http://127.0.0.1:{metrics_port}/metrics", timeout=3
    ) as rsp:
        return rsp.read().decode()


def run_drill(deadline_ms: float = None, request_timeout_ms: int = 200):
    """The drill against two live subprocess servers; returns the artifact
    dict with a ``failures`` list (empty = drill passed)."""
    from sentinel_tpu.engine import TokenStatus
    from sentinel_tpu.ha import (
        FailoverTokenClient,
        FallbackAction,
        FallbackRule,
        LocalFallbackPolicy,
    )

    if deadline_ms is None:
        from sentinel_tpu.core.config import SentinelConfig
        from sentinel_tpu.ha.failover import KEY_FAILOVER_DEADLINE_MS

        deadline_ms = SentinelConfig.get_float(KEY_FAILOVER_DEADLINE_MS, 500.0)
    failures = []
    primary_proc, primary_port, _ = _spawn_server()
    standby_proc, standby_port, _ = _spawn_server()
    # the fallback rule throttles to a local window so the all-down phase
    # measures a real blocked-rate, not a constant verdict
    policy = LocalFallbackPolicy(
        [FallbackRule(DRILL_FLOW, FallbackAction.THROTTLE,
                      count=N_FALLBACK_PROBES / 4)]
    )
    client = FailoverTokenClient(
        [("127.0.0.1", primary_port), ("127.0.0.1", standby_port)],
        timeout_ms=request_timeout_ms,
        failure_threshold=1,
        deadline_ms=deadline_ms,
        fallback=policy,
    )
    standby = f"127.0.0.1:{standby_port}"
    converged_ms = None
    try:
        # steady load on the primary until verdicts flow
        warm_deadline = time.monotonic() + 30.0
        while time.monotonic() < warm_deadline:
            if client.request_token(DRILL_FLOW).ok:
                break
        else:
            failures.append("primary never served before the kill")
        for _ in range(50):
            client.request_token(DRILL_FLOW)

        # phase 1: kill the primary mid-load, converge on the standby
        primary_proc.kill()
        primary_proc.wait()
        t0 = time.monotonic()
        while time.monotonic() - t0 < 10.0:
            r = client.request_token(DRILL_FLOW)  # must never raise
            if r.ok and str(client.active_endpoint) == standby:
                converged_ms = (time.monotonic() - t0) * 1e3
                break
        if converged_ms is None:
            failures.append("never converged on the standby")
        elif converged_ms > deadline_ms:
            failures.append(
                f"convergence {converged_ms:.1f}ms exceeds the "
                f"{deadline_ms:.0f}ms deadline"
            )
        for _ in range(50):
            if not client.request_token(DRILL_FLOW).ok:
                failures.append("standby dropped a request after takeover")
                break

        # phase 2: kill the standby too — every request must resolve via
        # the per-rule local fallback, never an unhandled exception
        standby_proc.kill()
        standby_proc.wait()
        resolved = blocked = 0
        try:
            for _ in range(N_FALLBACK_PROBES):
                r = client.request_token(DRILL_FLOW)
                resolved += 1
                if r.status == TokenStatus.BLOCKED:
                    blocked += 1
        except Exception as e:  # the one outcome the subsystem forbids
            failures.append(f"fallback raised: {e!r}")
        if resolved and not blocked:
            failures.append(
                "throttle fallback never blocked above the local window"
            )
        stats = policy.stats()
    finally:
        client.close()
        for proc in (primary_proc, standby_proc):
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    return {
        "failover_convergence_ms": (
            round(converged_ms, 1) if converged_ms is not None else None
        ),
        "deadline_ms": deadline_ms,
        "fallback_requests": resolved,
        "fallback_blocked_rate": stats["blocked_rate"],
        "endpoints": client.health_snapshot(),
        "failures": failures,
    }


def run_overload_drill(seconds: float = 2.5, probe_timeout_ms: int = 500):
    """Saturation drill: drive an IN-PROCESS token server at 2× its
    measured closed-loop capacity and verify the overload contract:

    - ≥99% of offered frames are ANSWERED (a verdict or an explicit
      OVERLOAD refusal — silence only for deliberately deadline-shed
      frames, which this drill doesn't send),
    - ``sentinel_server_shed_total`` moved (the server really shed),
    - a concurrent ``FailoverTokenClient`` health probe NEVER evicts the
      overloaded-but-alive server (OVERLOAD is proof of life),
    - the brownout escalation wrote a **black-box dump** whose per-tenant
      SLO block identifies the flooding namespace: the flood targets the
      ``flood`` namespace's flows only, so its burn/over counts must
      dwarf the bystander ``steady`` namespace's (docs/OBSERVABILITY.md).

    Returns the artifact dict with a ``failures`` list (empty = passed).
    """
    import glob
    import tempfile

    import numpy as np

    from benchmarks.serve_client import run_closed, run_open
    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.core.config import SentinelConfig
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
    from sentinel_tpu.engine.rules import ThresholdMode
    from sentinel_tpu.ha import FailoverTokenClient
    from sentinel_tpu.metrics.server import server_metrics
    from sentinel_tpu.overload import AdmissionController, OverloadConfig
    from sentinel_tpu.trace import blackbox
    from sentinel_tpu.trace import ring as trace_ring
    from sentinel_tpu.trace.slo import KEY_OBJECTIVE_MS
    from sentinel_tpu.trace.slo import reset_slo_plane_for_tests

    failures = []
    svc = DefaultTokenService(
        EngineConfig(max_flows=64, max_namespaces=4, batch_size=256)
    )
    # two tenants: the open-loop flood below targets flows 0-3 ONLY, so
    # the dump's per-tenant attribution must name "flood", not "steady"
    svc.load_rules(
        [ClusterFlowRule(f, 1e9, ThresholdMode.GLOBAL,
                         namespace="flood" if f < 4 else "steady")
         for f in range(8)]
    )
    # a generous latency objective keeps the bystander tenant's burn near
    # zero on this batching CPU path: only refusals (all aimed at the
    # flooded tenant) spend error budget
    SentinelConfig.set(KEY_OBJECTIVE_MS, "50")
    reset_slo_plane_for_tests()
    blackbox_dir = tempfile.mkdtemp(prefix="sentinel-blackbox-drill-")
    blackbox.configure(blackbox_dir, window_s=30.0, min_interval_s=0.5)
    trace_ring.arm(sample=0.01)
    # a small bounded queue + capped fusion make saturation honest: the
    # batcher can't amortize an arbitrary backlog into one device step,
    # and the front door answers OVERLOAD the moment the queue fills.
    # The admission ladder is tightened (low BDP floor, short sustain) so
    # the 2x flood demonstrably escalates the brownout — the trigger the
    # black-box gate below depends on.
    server = TokenServer(
        svc, port=0, max_queue=32, max_batch=128, max_inflight=1,
        inline_below=0,
        overload=AdmissionController(OverloadConfig(
            headroom_shed=4.0, headroom_degrade=64.0, min_bdp=64.0,
            sustain_ms=100.0,
        )),
    )
    server.start()
    sm = server_metrics()
    probe_stats = {"probes": 0, "resolved": 0, "evicted": False}
    stop_probe = None
    try:
        closed = run_closed(
            server.port, batch=64, pipeline=4, seconds=1.0, n_flows=8,
            seed=7,
        )
        capacity = closed["verdicts_ok"] / closed["wall_s"]
        if capacity <= 0:
            failures.append("capacity measurement produced zero verdicts")
            capacity = 10_000.0

        import threading

        stop_probe = threading.Event()
        fc = FailoverTokenClient(
            [("127.0.0.1", server.port)], timeout_ms=probe_timeout_ms,
            failure_threshold=3,
        )

        def probe():
            while not stop_probe.is_set():
                probe_stats["probes"] += 1
                try:
                    fc.request_token(0)
                    probe_stats["resolved"] += 1
                except Exception:
                    pass
                if fc.health_snapshot()[0]["state"] != "CLOSED":
                    probe_stats["evicted"] = True
                time.sleep(0.02)

        pt = threading.Thread(target=probe)
        pt.start()

        # open-loop flood at 2× capacity; escalate (double) until the
        # server demonstrably shed — a too-fast server is not a pass
        open_doc = None
        shed_delta = {}
        rate = 2.0 * capacity
        shed0 = sm.shed_totals()
        for _attempt in range(3):
            # n_flows=4: every flooded row belongs to the "flood" tenant
            open_doc = run_open(
                server.port, batch=64, rate=rate, seconds=seconds,
                n_flows=4, seed=11, window=100_000,
            )
            shed1 = sm.shed_totals()
            shed_delta = {
                k: shed1.get(k, 0) - shed0.get(k, 0)
                for k in set(shed0) | set(shed1)
                if shed1.get(k, 0) - shed0.get(k, 0) > 0
            }
            if sum(shed_delta.values()) > 0:
                break
            rate *= 2.0
        stop_probe.set()
        pt.join(timeout=5)
        fc.close()

        sent = open_doc["frames_sent"]
        lost = open_doc["frames_lost"]
        answered_frac = (sent - lost) / sent if sent else 0.0
        rtt = open_doc["rtt_ms"]
        p99_ms = float(np.percentile(np.asarray(rtt), 99)) if rtt else None

        if answered_frac < 0.99:
            failures.append(
                f"only {answered_frac:.4f} of offered frames answered "
                "(contract: >= 0.99 at 2x saturation)"
            )
        if sum(shed_delta.values()) == 0:
            failures.append(
                "sentinel_server_shed_total never moved under saturation"
            )
        if probe_stats["evicted"]:
            failures.append(
                "failover probe evicted the overloaded-but-alive server"
            )
        if probe_stats["probes"] and not probe_stats["resolved"]:
            failures.append("no health probe resolved during the flood")

        # -- black-box gate: the escalation dumped, the dump parses, and
        # its per-tenant SLO block names the flooding namespace
        bb_doc = {"path": None, "parsed": False}
        dumps = sorted(glob.glob(os.path.join(blackbox_dir, "*.json")))
        if not dumps:
            failures.append(
                "brownout escalation wrote no black-box dump "
                f"(admission={server.overload.snapshot()})"
            )
        else:
            try:
                with open(dumps[-1]) as f:
                    doc = json.load(f)
                tenants = doc.get("slo", {}).get("tenants", {})
                flood_over = (
                    tenants.get("flood", {}).get("windows", {})
                    .get("1m", {}).get("over", 0)
                )
                steady_over = (
                    tenants.get("steady", {}).get("windows", {})
                    .get("1m", {}).get("over", 0)
                )
                bb_doc = {
                    "path": dumps[-1],
                    "parsed": doc.get("schema") == "sentinel-blackbox/1",
                    "reason": doc.get("reason"),
                    "events": len(doc.get("events", [])),
                    "floodOver1m": flood_over,
                    "steadyOver1m": steady_over,
                    "floodBurn1m": (
                        tenants.get("flood", {}).get("burnRate", {})
                        .get("1m")
                    ),
                }
                if not bb_doc["parsed"]:
                    failures.append(
                        f"black-box dump schema wrong: {doc.get('schema')}"
                    )
                if not str(doc.get("reason", "")).startswith("brownout"):
                    failures.append(
                        "black-box dump reason is not the brownout "
                        f"escalation: {doc.get('reason')}"
                    )
                if flood_over <= 2 * steady_over or flood_over == 0:
                    failures.append(
                        "black-box SLO block failed to identify the "
                        f"flooding namespace (flood over={flood_over}, "
                        f"steady over={steady_over})"
                    )
            except Exception as e:
                failures.append(f"black-box dump unparseable: {e!r}")
    finally:
        if stop_probe is not None:
            stop_probe.set()
        server.stop()
        trace_ring.disarm()
        blackbox.configure(None)
        with SentinelConfig._lock:
            SentinelConfig._props.pop(KEY_OBJECTIVE_MS, None)
    return {
        "capacity_vps": round(capacity),
        "offered_rate_vps": round(rate),
        "frames_sent": sent,
        "frames_answered": sent - lost,
        "answered_frac": round(answered_frac, 4),
        "p99_ms": round(p99_ms, 2) if p99_ms is not None else None,
        "shed_by_reason": shed_delta,
        "admission": server.overload.snapshot(),
        "probe": probe_stats,
        "blackbox": bb_doc,
        "failures": failures,
    }


def measure_param_delta_bytes(
    n_values: int = 3000,
    chunk: int = 60,
) -> dict:
    """Per-tick param replication wire cost, slim vs fat, on identical
    traffic: two in-process services — one with the SF slim twin enabled
    (deltas ship ``param_slim`` rows), one with ``slim_width=0`` (deltas
    ship full fat rows) — absorb the same value stream, then each exports
    one delta through the real wire codec (``encode_delta_blob``). The
    slim blob's bytes are fed to ``ha_metrics().add_repl_bytes`` so
    ``sentinel_repl_bytes_total`` shows what a slim-shipping tick costs.
    Returns ``{"fat": int, "slim": int, "ratio": float}``; the drill gates
    on ratio ≥ 4 (docs/SKETCHES.md)."""
    import numpy as np

    from sentinel_tpu.cluster.token_service import (
        ClusterParamFlowRule,
        DefaultTokenService,
    )
    from sentinel_tpu.engine import EngineConfig
    from sentinel_tpu.engine.param import ParamConfig
    from sentinel_tpu.ha import replication as R
    from sentinel_tpu.metrics.ha import ha_metrics

    cfg = EngineConfig(max_flows=16, max_namespaces=4, batch_size=64)
    rng = np.random.default_rng(0x5A15A)
    vals = rng.integers(-2 ** 63, 2 ** 63 - 1, size=n_values, dtype=np.int64)
    sizes = {}
    for label, slim_width in (("slim", 256), ("fat", 0)):
        svc = DefaultTokenService(
            cfg,
            param_config=ParamConfig(
                max_param_rules=32, impl="jax", slim_width=slim_width
            ),
        )
        svc.load_param_rules(
            [ClusterParamFlowRule(flow_id=5, count=1e9),
             ClusterParamFlowRule(flow_id=6, count=1e9)]
        )
        svc.replication_enable()
        for fid in (5, 6):
            for off in range(0, n_values, chunk):
                svc.request_params_token(
                    fid, 1, [int(h) for h in vals[off:off + chunk]]
                )
        sizes[label] = len(R.encode_delta_blob(svc.export_delta()))
    ha_metrics().add_repl_bytes(sizes["slim"])
    return {
        "fat": sizes["fat"],
        "slim": sizes["slim"],
        "ratio": round(sizes["fat"] / max(sizes["slim"], 1), 2),
    }


def run_replication_drill(
    count: float = 300.0,
    repl_interval_ms: float = 100.0,
    promote_after_ms: float = 1000.0,
    bucket_ms: int = 500,
    drive_rate: float = 200.0,
):
    """Warm-standby lossless-failover drill: SIGKILL the primary MID-WINDOW
    and verify the promoted standby keeps enforcing the window the primary
    already half-spent.

    Topology: primary streams deltas every ``repl_interval_ms`` to a
    standby whose watchdog self-promotes after ``promote_after_ms`` of
    silence. A paced client admits against a finite window of ``count``
    tokens. Rule counts are per-SECOND rates (the engine scales the
    threshold by the window length), so the children get
    ``count / window_s`` as their rule count; with ``bucket_ms=500`` the
    window is 5s — wide enough to hold the whole drill, and the drill
    stays under the earliest possible bucket-rotation point (~4.5s) so
    expiring buckets can't silently refill the window. Invariants:

    - every request RESOLVES throughout (verdict / STANDBY walk-on /
      fallback block — never an exception);
    - total admissions across both servers stay within ``count`` plus the
      staleness budget — one delta-ship interval's worth of tokens at the
      measured admission rate (the only state a SIGKILL can lose);
    - the promoted standby actually BLOCKS (proof it inherited the
      half-spent window rather than starting fresh);
    - ``sentinel_repl_lag_ms`` and the delta counters are live on both
      metrics surfaces;
    - per-tick param replication bytes: SF slim deltas come in ≥4× under
      fat-row deltas for identical traffic (``measure_param_delta_bytes``),
      recorded in the artifact and ``sentinel_repl_bytes_total``.
    """
    from sentinel_tpu.engine import TokenStatus
    from sentinel_tpu.ha import (
        FailoverTokenClient,
        FallbackAction,
        FallbackRule,
        LocalFallbackPolicy,
    )

    failures = []
    # EngineConfig default n_buckets=10: window = bucket_ms * 10
    window_s = bucket_ms * 10 / 1000.0
    rule_qps = count / window_s
    common = [
        "--count", str(rule_qps), "--bucket-ms", str(bucket_ms),
        "--repl-interval-ms", str(repl_interval_ms),
    ]
    standby_proc, standby_port, standby_mport = _spawn_server(
        extra=common + [
            "--standby-of", "primary",
            "--promote-after-ms", str(promote_after_ms),
        ]
    )
    primary_proc, primary_port, primary_mport = _spawn_server(
        extra=common + ["--replicate-to", f"127.0.0.1:{standby_port}"]
    )
    # fallback BLOCKS: the promotion gap must not admit locally, or the
    # over-admission measure would be polluted by client-side passes
    policy = LocalFallbackPolicy(
        [FallbackRule(DRILL_FLOW, FallbackAction.BLOCK)]
    )
    client = FailoverTokenClient(
        [("127.0.0.1", primary_port), ("127.0.0.1", standby_port)],
        timeout_ms=200, failure_threshold=1, fallback=policy,
    )
    period = 1.0 / drive_rate
    admitted_fill = admitted_post = resolved = standby_blocks = 0
    fill_rate = None
    repl_lag_live = False
    converge_ms = None
    over_admission = budget = 0
    standby_metrics = {}
    try:
        # warm until the primary serves, then scrape its sender-side
        # replication gauges while it is still alive
        warm_deadline = time.monotonic() + 30.0
        while time.monotonic() < warm_deadline:
            if client.request_token(DRILL_FLOW).ok:
                admitted_fill += 1
                break
        else:
            failures.append("primary never served before the kill")
        # fill phase: paced admissions to the middle of the window
        t_fill = time.monotonic()
        next_t = t_fill
        while admitted_fill < count / 2:
            next_t += period
            time.sleep(max(0.0, next_t - time.monotonic()))
            r = client.request_token(DRILL_FLOW)
            resolved += 1
            if r.ok:
                admitted_fill += 1
            if time.monotonic() - t_fill > 5.0:
                failures.append("fill phase never reached count/2")
                break
        fill_wall = max(time.monotonic() - t_fill, 1e-6)
        fill_rate = admitted_fill / fill_wall

        def _shipped(body: str) -> float:
            needle = 'sentinel_repl_deltas_total{event="shipped"}'
            for line in body.splitlines():
                if line.startswith(needle):
                    return float(line.split()[-1])
            return 0.0

        # grace: under dispatch load the sender's effective cadence can
        # stretch well past repl_interval_ms (the delta collector contends
        # with the dispatch hot path for the service lock), so "kill one
        # interval after the last request" would measure scheduler noise,
        # not replication. Instead keep the window live at a low rate and
        # watch the shipped counter. One increment is not enough: that
        # delta may have been CAPTURED mid-fill and merely acked late (a
        # slow ship under load), silently missing the fill's tail. Two
        # increments past the baseline guarantee coverage — the second
        # delta is captured after the first one's post-fill ack, so it
        # includes every fill admission. Kill right after it.
        base_shipped = cur_shipped = 0.0
        if primary_mport:
            try:
                body = _scrape(primary_mport)
            except Exception as e:
                failures.append(f"primary metrics scrape failed: {e!r}")
                body = ""
            repl_lag_live = "sentinel_repl_lag_ms" in body
            base_shipped = cur_shipped = _shipped(body)
            grace_deadline = time.monotonic() + 2.0
            while time.monotonic() < grace_deadline:
                if client.request_token(DRILL_FLOW).ok:
                    admitted_fill += 1
                resolved += 1
                try:
                    cur_shipped = _shipped(_scrape(primary_mport))
                except Exception:
                    pass
                if cur_shipped >= base_shipped + 2:
                    break
                time.sleep(0.05)
            if cur_shipped <= 0:
                failures.append("primary never shipped a delta")

        # the kill: right after an acked delta ship
        primary_proc.kill()
        primary_proc.wait()
        t_kill = time.monotonic()
        # drive through the outage at the same pace; the watchdog promotes
        # the standby, the client walks over, and the half-spent window
        # keeps being enforced
        # bounded so warm+fill+grace+outage stays inside one window: a
        # token admitted at t leaves the rolling window no sooner than
        # t+4.5s (bucket rotation), after which capacity would silently
        # refill and pollute the over-admission measure
        next_t = time.monotonic()
        while time.monotonic() - t_kill < promote_after_ms / 1000.0 + 1.5:
            next_t += period
            time.sleep(max(0.0, next_t - time.monotonic()))
            r = client.request_token(DRILL_FLOW)  # must never raise
            resolved += 1
            if r is None:
                failures.append("request returned None")
                continue
            on_standby = (
                str(client.active_endpoint) == f"127.0.0.1:{standby_port}"
            )
            if r.ok:
                admitted_post += 1
                if on_standby and converge_ms is None:
                    converge_ms = (time.monotonic() - t_kill) * 1e3
            elif on_standby and r.status == TokenStatus.BLOCKED:
                standby_blocks += 1
        total_admitted = admitted_fill + admitted_post
        # staleness budget: what one lost ship interval can re-admit, at
        # the measured fill rate (+1 in-flight batch of slack). The slack
        # used to be +2 when a full fat-sketch delta could stretch the
        # sender's effective cadence past repl_interval_ms under load; SF
        # slim deltas (sketch/slim.py) cut the param payload ≥4×, so one
        # batch of slack is enough — a looser gate would hide a sender
        # falling back to fat shipping.
        budget = int(fill_rate * repl_interval_ms / 1000.0) + 1
        over_admission = max(0, int(total_admitted - count))
        if converge_ms is None:
            failures.append("standby never served after the kill")
        if over_admission > budget:
            failures.append(
                f"over-admitted {over_admission} tokens "
                f"(budget {budget} = one {repl_interval_ms:.0f}ms ship "
                f"interval at {fill_rate:.0f}/s)"
            )
        if not standby_blocks:
            failures.append(
                "promoted standby never blocked — replicated window state "
                "was not enforced"
            )
        if standby_mport:
            try:
                body = _scrape(standby_mport)
            except Exception as e:
                failures.append(f"standby metrics scrape failed: {e!r}")
                body = ""
            prefix = "sentinel_repl_deltas_total{event="
            for line in body.splitlines():
                if line.startswith(prefix):
                    key = line[len(prefix):].split("}")[0].strip('"')
                    standby_metrics[key] = float(line.split()[-1])
            if standby_metrics.get("promoted", 0) < 1:
                failures.append("standby metrics show no promotion event")
            if standby_metrics.get("applied", 0) < 1:
                failures.append("standby metrics show no applied delta")
    finally:
        client.close()
        for proc in (primary_proc, standby_proc):
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    # per-tick param replication wire cost, slim vs fat, on identical
    # in-process traffic — the measurement that justifies the tightened
    # one-batch staleness slack above
    try:
        param_delta_bytes = measure_param_delta_bytes()
    except Exception as e:
        param_delta_bytes = {"fat": 0, "slim": 0, "ratio": 0.0}
        failures.append(f"param delta byte measure failed: {e!r}")
    else:
        if param_delta_bytes["ratio"] < 4.0:
            failures.append(
                f"slim param deltas only {param_delta_bytes['ratio']:.1f}x "
                f"smaller than fat (need >= 4x): "
                f"{param_delta_bytes['slim']}B vs {param_delta_bytes['fat']}B"
            )
    return {
        "window_tokens": count,
        "param_delta_bytes": param_delta_bytes,
        "rule_qps": rule_qps,
        "repl_interval_ms": repl_interval_ms,
        "fill_rate_vps": round(fill_rate, 1) if fill_rate else None,
        "admitted_before_kill": admitted_fill,
        "admitted_after_kill": admitted_post,
        "over_admission": over_admission,
        "staleness_budget": budget,
        "promote_convergence_ms": (
            round(converge_ms, 1) if converge_ms is not None else None
        ),
        "standby_blocks": standby_blocks,
        "requests_resolved": resolved,
        "repl_lag_gauge_live": repl_lag_live,
        "standby_repl_events": standby_metrics,
        "failures": failures,
    }


def run_rebalance_drill(
    count: float = 300.0,
    bucket_ms: int = 500,
    drive_rate: float = 150.0,
):
    """Elastic-fleet drill: move a namespace between two LIVE token servers
    under sustained load and verify the lossless-handoff contract.

    Topology: two in-process ``TokenServer``s (in-process because the move
    coordinator runs inside the source server's process by design — it
    needs the service's export hook); a ``RoutingTokenClient`` with a local
    BLOCK fallback paces admissions against a fixed window of ``count``
    tokens. With ``bucket_ms=500`` the window is 5s; the whole loaded phase
    stays under the ~4.5s bucket-rotation point so expiry can't refill the
    window mid-measure. Phases and invariants:

    - **abort atomicity** (quiet): a chaos ``conn_reset`` kills the move's
      connection mid-protocol. The move must FAIL, the source must remain
      the sole owner with BIT-EQUAL counters (export before == after), and
      the destination must have staged nothing.
    - **move under load**: half-way into the window the namespace moves for
      real. Every request must RESOLVE (verdict, redirect follow-through,
      or fallback — never an exception), total admissions across BOTH
      servers must stay within ``count`` (over-admission exactly 0: the
      handoff ships the spent window, so the destination continues it
      rather than starting fresh), and the routing client must converge on
      the new owner within ONE shard-map epoch bump (< 2 epochs crossed).
    """
    import threading as _threading

    import numpy as np

    from sentinel_tpu import chaos
    from sentinel_tpu.cluster.rebalance import (
        MoveCoordinator,
        ShardMapPublisher,
    )
    from sentinel_tpu.cluster.routing import RoutingTokenClient
    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig, TokenStatus
    from sentinel_tpu.engine.rules import ThresholdMode
    from sentinel_tpu.ha import FallbackAction, FallbackRule, LocalFallbackPolicy
    from sentinel_tpu.metrics.ha import ha_metrics

    failures = []
    window_s = bucket_ms * 10 / 1000.0  # EngineConfig default n_buckets=10
    rule_qps = count / window_s
    cfg = EngineConfig(
        max_flows=64, max_namespaces=4, batch_size=64, bucket_ms=bucket_ms
    )
    svc_src = DefaultTokenService(cfg)
    svc_dst = DefaultTokenService(cfg)
    svc_src.load_rules(
        [ClusterFlowRule(DRILL_FLOW, rule_qps, ThresholdMode.GLOBAL, "drill"),
         ClusterFlowRule(7, 1e6, ThresholdMode.GLOBAL, "warm")]
    )
    srv_src = TokenServer(svc_src, port=0)
    srv_dst = TokenServer(svc_dst, port=0)
    srv_src.start()
    srv_dst.start()
    src_ep = f"127.0.0.1:{srv_src.port}"
    dst_ep = f"127.0.0.1:{srv_dst.port}"
    pub = ShardMapPublisher()
    coord = MoveCoordinator(svc_src, self_endpoint=src_ep, publisher=pub)
    policy = LocalFallbackPolicy(
        [FallbackRule(DRILL_FLOW, FallbackAction.BLOCK)]
    )
    client = RoutingTokenClient(
        timeout_ms=500,
        namespace_of={DRILL_FLOW: "drill", 7: "warm"},
        pod_of={"drill": src_ep, "warm": src_ep},
        endpoints={src_ep: ("127.0.0.1", srv_src.port),
                   dst_ep: ("127.0.0.1", srv_dst.port)},
        fallback=policy,
        shard_maps=pub,
    )
    admitted = blocked = resolved = raised = 0
    move_result = {"ok": False, "wall_ms": None}
    abort_ok = bit_equal = sole_owner = False
    epochs_crossed = converge_requests = None
    try:
        # warm the full move path (export → codec → import → device prep)
        # on a throwaway namespace so the timed phase measures the
        # protocol, not JAX compilation
        if not coord.move_namespace("warm", dst_ep):
            failures.append(f"warm move failed: {coord.last_error!r}")
        coord.release("warm")

        # phase 1 — abort atomicity, no traffic in flight so the counter
        # comparison is exact: the ONLY conn_reset probe between arm and
        # disarm is the coordinator's own move channel
        for _ in range(5):
            if client.request_token(DRILL_FLOW).ok:
                admitted += 1
            resolved += 1
        doc0 = svc_src.export_namespace_state("drill")
        chaos.arm("conn_reset:n=1", seed=7)
        try:
            aborted_move = coord.move_namespace("drill", dst_ep)
        finally:
            chaos.disarm()
        abort_ok = not aborted_move
        if aborted_move:
            failures.append("chaos-cut move reported success")
        doc1 = svc_src.export_namespace_state("drill")
        bit_equal = bool(
            np.array_equal(doc0["flow_sums"], doc1["flow_sums"])
            and np.array_equal(doc0["ns_sum"], doc1["ns_sum"])
        )
        if not bit_equal:
            failures.append("aborted move changed the source's counters")
        sole_owner = not svc_dst.export_namespace_state("drill")["rules"]
        if not sole_owner:
            failures.append("aborted move left rules on the destination")
        r = client.request_token(DRILL_FLOW)
        if r.status not in (TokenStatus.OK, TokenStatus.BLOCKED):
            failures.append(f"source not serving after abort: {r.status!r}")
        elif r.ok:
            admitted += 1
        resolved += 1

        # phase 2 — the real move, mid-window, under sustained load
        epoch0 = client.epoch
        period = 1.0 / drive_rate
        t0 = time.monotonic()
        next_t = t0
        mover = None

        def _move():
            t = time.monotonic()
            move_result["ok"] = coord.move_namespace("drill", dst_ep)
            move_result["wall_ms"] = round(
                (time.monotonic() - t) * 1e3, 1
            )

        while time.monotonic() - t0 < 3.2:
            next_t += period
            time.sleep(max(0.0, next_t - time.monotonic()))
            if mover is None and admitted >= count / 2:
                mover = _threading.Thread(target=_move)
                mover.start()
            try:
                r = client.request_token(DRILL_FLOW)
            except Exception:
                raised += 1
                continue
            resolved += 1
            if r.ok:
                admitted += 1
            elif r.status == TokenStatus.BLOCKED:
                blocked += 1
        if mover is None:
            failures.append(
                f"load never half-spent the window ({admitted} admissions)"
            )
        else:
            mover.join(timeout=30)
            if not move_result["ok"]:
                failures.append(f"live move failed: {coord.last_error!r}")
        epochs_crossed = client.epoch - epoch0
        if raised:
            failures.append(f"{raised} requests raised during the move")
        over_admission = max(0, int(admitted - count))
        if over_admission != 0:
            failures.append(
                f"over-admitted {over_admission} of {count:.0f} window "
                "tokens across the move"
            )
        if epochs_crossed is not None and epochs_crossed >= 2:
            failures.append(
                f"client crossed {epochs_crossed} routing epochs "
                "(contract: converge within 1)"
            )
        # post-move convergence: the client must reach the new owner
        # without further redirects or failures
        converge_requests = 0
        for _ in range(20):
            r = client.request_token(DRILL_FLOW)
            converge_requests += 1
            if r.status in (TokenStatus.OK, TokenStatus.BLOCKED):
                break
        else:
            failures.append("client never converged on the destination")
        reb = ha_metrics().snapshot()["rebalance"]
        if reb["redirectsTotal"] < 1:
            failures.append("no MOVED redirect was ever answered")
        if not reb["events"].get("commit"):
            failures.append("rebalance metrics show no commit event")
    finally:
        client.close()
        srv_src.stop()
        srv_dst.stop()
    return {
        "window_tokens": count,
        "rule_qps": rule_qps,
        "admitted": admitted,
        "blocked": blocked,
        "requests_resolved": resolved,
        "requests_raised": raised,
        "over_admission": max(0, int(admitted - count)),
        "abort_atomic": abort_ok and bit_equal and sole_owner,
        "move_wall_ms": move_result["wall_ms"],
        "epochs_crossed": epochs_crossed,
        "converge_requests": converge_requests,
        "rebalance_metrics": ha_metrics().snapshot()["rebalance"],
        "failures": failures,
    }


def run_lease_drill(
    count: float = 300.0,
    repl_interval_ms: float = 100.0,
    promote_after_ms: float = 1000.0,
    bucket_ms: int = 700,
    drive_rate: float = 200.0,
    lease_ttl_ms: float = 4000.0,
    lease_want: int = 60,
):
    """Lease crash drill: SIGKILL the primary WITH LEASES OUTSTANDING and
    verify the wire-rev-5 over-admission bound.

    Charge-at-grant is the accounting that makes the bound provable: the
    full delegated slice lands in the window's LEASED column at grant time
    and replicates like any other event, so the promoted standby counts it
    without ever learning a lease existed. What a crash can lose is at most
    the unreplicated part of that charge — hence the gate::

        total admitted (fill + client-local + post-promotion)
            <= window count + outstanding-lease sum at the kill

    The drill fills half the window, waits for a post-fill delta ship (so
    wire-admission staleness is zero and the lease term is isolated),
    grants one lease, scrapes ``sentinel_lease_outstanding_tokens`` as the
    bound, SIGKILLs the primary, drains the client's lease slice locally
    (RPC-free — the primary is dead and admission continues), then drives
    the promoted standby until it blocks. Every request must resolve; the
    lease client must degrade to wire verdicts (never raise) once its
    slice is spent against a dead server."""
    from sentinel_tpu.cluster.client import TokenClient
    from sentinel_tpu.engine import TokenStatus

    failures = []
    window_s = bucket_ms * 10 / 1000.0  # EngineConfig default n_buckets=10
    rule_qps = count / window_s
    common = [
        "--count", str(rule_qps), "--bucket-ms", str(bucket_ms),
        "--repl-interval-ms", str(repl_interval_ms),
        "--lease-ttl-ms", str(lease_ttl_ms),
    ]
    standby_proc, standby_port, _standby_mport = _spawn_server(
        extra=common + [
            "--standby-of", "primary",
            "--promote-after-ms", str(promote_after_ms),
        ]
    )
    primary_proc, primary_port, primary_mport = _spawn_server(
        extra=common + ["--replicate-to", f"127.0.0.1:{standby_port}"]
    )
    wire = TokenClient("127.0.0.1", primary_port, timeout_ms=200)
    leaser = TokenClient("127.0.0.1", primary_port, timeout_ms=200,
                         lease=True, lease_want=lease_want)
    period = 1.0 / drive_rate
    admitted_fill = local_admits = standby_admits = standby_blocks = 0
    outstanding_tokens = 0.0
    lease_granted = False
    over_admission = 0

    def _counter(body: str, needle: str) -> float:
        for line in body.splitlines():
            if line.startswith(needle):
                return float(line.split()[-1])
        return 0.0

    try:
        # warm every jit path OUTSIDE the measured window, on WARM_FLOW's
        # unbounded rule: the plain decide kernel and the lease-grant
        # window sums both compile here, not mid-window
        warm_deadline = time.monotonic() + 30.0
        while time.monotonic() < warm_deadline:
            if wire.request_token(WARM_FLOW).ok:
                break
        else:
            failures.append("primary never served before the kill")
        warm_lease = TokenClient("127.0.0.1", primary_port, timeout_ms=500,
                                 lease=True, lease_want=8)
        try:
            if not warm_lease.request_token(WARM_FLOW).ok:
                failures.append("lease warmup on the warm flow failed")
        finally:
            warm_lease.close()  # returns the warm slice

        # fill: paced wire admissions to the middle of the window
        t_fill = time.monotonic()
        next_t = t_fill
        while admitted_fill < count / 2:
            next_t += period
            time.sleep(max(0.0, next_t - time.monotonic()))
            if wire.request_token(DRILL_FLOW).ok:
                admitted_fill += 1
            if time.monotonic() - t_fill > 5.0:
                failures.append("fill phase never reached count/2")
                break

        # quiesce, then wait for one delta ship CAPTURED AFTER the last
        # fill admission: wire-admission replication staleness is now zero,
        # so the over-admission gate below isolates the lease term
        shipped_needle = 'sentinel_repl_deltas_total{event="shipped"}'
        try:
            base_shipped = _counter(_scrape(primary_mport), shipped_needle)
            ship_deadline = time.monotonic() + 3.0
            while time.monotonic() < ship_deadline:
                if _counter(_scrape(primary_mport),
                            shipped_needle) > base_shipped:
                    break
                time.sleep(repl_interval_ms / 1000.0 / 2)
            else:
                failures.append("no delta shipped after the fill phase")
        except Exception as e:
            failures.append(f"primary metrics scrape failed: {e!r}")

        # the lease: one grant, then read the authoritative outstanding sum
        # off the primary's metrics surface — the crash bound
        r = leaser.request_token(DRILL_FLOW)
        if r is not None and r.ok:
            local_admits += 1
        lease_granted = leaser.lease_stats().get("granted", 0) >= 1
        if not lease_granted:
            failures.append("lease was never granted before the kill")
        try:
            outstanding_tokens = _counter(
                _scrape(primary_mport), "sentinel_lease_outstanding_tokens"
            )
        except Exception as e:
            failures.append(f"outstanding-lease scrape failed: {e!r}")
        if outstanding_tokens <= 0:
            failures.append(
                "primary reported no outstanding lease tokens at the kill"
            )
        # give the grant charge one ship interval (not required for the
        # bound — an unshipped charge IS the lease term — but it makes the
        # typical run's over-admission land near zero)
        time.sleep(repl_interval_ms / 1000.0 * 1.5)

        # the kill: leases outstanding, slice half-unspent
        primary_proc.kill()
        primary_proc.wait()
        t_kill = time.monotonic()

        # client-local admission continues against the DEAD primary: this
        # is exactly the over-admission a crashed grant can cost, and it
        # must degrade to wire verdicts (never raise) once the slice is
        # spent or the renew-ahead retires it
        for _ in range(5 * lease_want):
            try:
                r = leaser.request_token(DRILL_FLOW)
            except Exception as e:
                failures.append(f"lease client raised post-kill: {e!r}")
                break
            if r is None or not r.ok:
                break
            local_admits += 1

        # drive the promoted standby until the inherited window blocks
        standby = TokenClient("127.0.0.1", standby_port, timeout_ms=200)
        try:
            next_t = time.monotonic()
            deadline = t_kill + promote_after_ms / 1000.0 + 2.5
            while time.monotonic() < deadline:
                next_t += period
                time.sleep(max(0.0, next_t - time.monotonic()))
                try:
                    r = standby.request_token(DRILL_FLOW)
                except Exception as e:
                    failures.append(f"standby request raised: {e!r}")
                    break
                if r is None:
                    continue
                if r.ok:
                    standby_admits += 1
                elif r.status == TokenStatus.BLOCKED:
                    standby_blocks += 1
                    if standby_blocks >= 3:
                        break
        finally:
            standby.close()
        if not standby_blocks:
            failures.append(
                "promoted standby never blocked — the window (with its "
                "lease charge) was not inherited"
            )
        total = admitted_fill + local_admits + standby_admits
        over_admission = max(0, int(total - count))
        if over_admission > int(outstanding_tokens):
            failures.append(
                f"over-admitted {over_admission} tokens, above the "
                f"outstanding-lease bound of {int(outstanding_tokens)}"
            )
    finally:
        leaser.close()
        wire.close()
        for proc in (primary_proc, standby_proc):
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    return {
        "window_tokens": count,
        "lease_want": lease_want,
        "lease_ttl_ms": lease_ttl_ms,
        "lease_granted": lease_granted,
        "outstanding_tokens_at_kill": int(outstanding_tokens),
        "admitted_fill": admitted_fill,
        "local_admits": local_admits,
        "standby_admits": standby_admits,
        "standby_blocks": standby_blocks,
        "over_admission": over_admission,
        "client_lease_stats": leaser.lease_stats(),
        "failures": failures,
    }


def run_hier_drill(
    budget_qps: float = 200.0,
    bucket_ms: int = 100,
    reconcile_ms: float = 200.0,
    chaos_seed: int = 7,
):
    """Two-pod hierarchical-limit drill: one GLOBAL budget split across two
    LIVE token servers by the tier-3 coordinator, with a skewed-demand flip.

    Topology: pod A co-hosts the ``GlobalBudgetCoordinator`` behind its
    ordinary front door (the rev-5 SHARE_*/DEMAND_REPORT type bytes need no
    extra port); both pods run a ``PodShareAgent`` against that door over
    real TCP. The drill paces agent ticks and reconcile passes ITSELF (the
    background threads stay off) so convergence is counted in ticks, not
    wall-clock noise. Phases and gates:

    - **bootstrap**: no demand → water-fill's equal split, shares conserve
      the budget exactly.
    - **skew to A**: a demand burst on pod A must pull A's share to ≥ 2×
      B's within 3 reconcile ticks of the report landing.
    - **flip to B**: demand moves to pod B; once A's old demand drains out
      of its sliding window and the coordinator re-targets, shares must
      converge (B ≥ 2× A) within 3 further ticks.
    - **zero cross-pod hops**: a decision burst on both pods with the
      control plane quiet must move the agents' RPC counters by exactly 0
      — admission is all client-to-own-pod.
    - **live over-admission**: both pods driven flat-out for one window
      admit ≤ global budget + one reconcile interval's worth (the hold
      rotation-decay → re-top gap, docs/CLUSTER_HA.md).
    - **chaos cut + coordinator dark**: a seeded conn_reset mid-tick, then
      the coordinator detached outright; agents must keep the last share
      (never raise, never unpin the hold), and a dark flat-out window
      admits ≤ Σ outstanding shares + the same slack.
    """
    from sentinel_tpu import chaos
    from sentinel_tpu.cluster.client import TokenClient
    from sentinel_tpu.cluster.hierarchy import (
        GlobalBudgetCoordinator,
        GlobalFlowBudget,
        PodShareAgent,
    )
    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
    from sentinel_tpu.engine.rules import ThresholdMode

    failures = []
    window_s = bucket_ms * 10 / 1000.0  # EngineConfig default n_buckets=10
    budget_tokens = int(budget_qps * window_s)
    # the documented bound: what one reconcile interval can leak through
    # hold rotation-decay before the next tick re-tops the hold
    slack_tokens = max(2, int(budget_tokens * reconcile_ms / (window_s * 1e3)))
    cfg = EngineConfig(
        max_flows=64, max_namespaces=4, batch_size=64, bucket_ms=bucket_ms
    )
    svcA = DefaultTokenService(cfg)
    svcB = DefaultTokenService(cfg)
    for svc in (svcA, svcB):
        svc.load_rules(
            [ClusterFlowRule(DRILL_FLOW, budget_qps, ThresholdMode.GLOBAL),
             ClusterFlowRule(WARM_FLOW, 1e9, ThresholdMode.GLOBAL)]
        )
    coord = GlobalBudgetCoordinator(
        [GlobalFlowBudget(DRILL_FLOW, budget_qps, window_s)],
        share_ttl_ms=30_000, reconcile_ms=reconcile_ms,
    )
    svcA.attach_hierarchy(coord)
    srvA = TokenServer(svcA, port=0, metrics_port=0)
    srvB = TokenServer(svcB, port=0, metrics_port=0)
    srvA.start()
    srvB.start()
    coord_ep = f"127.0.0.1:{srvA.port}"
    agA = PodShareAgent(svcA, [coord_ep], "pod-a", [DRILL_FLOW], tick_ms=100)
    agB = PodShareAgent(svcB, [coord_ep], "pod-b", [DRILL_FLOW], tick_ms=100)
    clA = TokenClient("127.0.0.1", srvA.port, timeout_ms=500)
    clB = TokenClient("127.0.0.1", srvB.port, timeout_ms=500)

    def _round():
        agA.tick()
        agB.tick()
        coord.reconcile_once()

    def _burst(cl, n, fid=DRILL_FLOW):
        ok = 0
        for _ in range(n):
            r = cl.request_token(fid)
            if r is not None and r.ok:
                ok += 1
        return ok

    def _shares():
        return (agA.shares().get(DRILL_FLOW, 0),
                agB.shares().get(DRILL_FLOW, 0))

    def _drain(rounds_dark=False):
        """Let DRILL_FLOW's sliding windows empty (real time — demand and
        admissions both decay by bucket rotation), re-topping holds with
        control-plane rounds along the way."""
        deadline = time.monotonic() + window_s + 3 * bucket_ms / 1e3
        while time.monotonic() < deadline:
            if rounds_dark:
                agA.tick()
                agB.tick()
            else:
                _round()
            time.sleep(2 * bucket_ms / 1e3)

    bootstrap = skew = flip = {}
    decision_rpcs = None
    live = dark = {}
    hier_series_live = False
    try:
        # warm the jit paths on the unbounded flow
        warm_deadline = time.monotonic() + 60.0
        while time.monotonic() < warm_deadline:
            if _burst(clA, 1, WARM_FLOW) and _burst(clB, 1, WARM_FLOW):
                break
        else:
            failures.append("pods never served the warm flow")

        # phase 1 — bootstrap: zero demand → equal split, budget conserved
        _round()
        _round()
        sA, sB = _shares()
        bootstrap = {"share_a": sA, "share_b": sB}
        if sA + sB > budget_tokens:
            failures.append(
                f"bootstrap shares {sA}+{sB} exceed the {budget_tokens} "
                "global budget"
            )
        if abs(sA - sB) > 1 or sA == 0:
            failures.append(
                f"bootstrap split {sA}/{sB} is not the equal water-fill"
            )

        # phase 2 — skew to A: burst demand, converge within 3 ticks of
        # the report landing (the first _round below ships the report)
        _burst(clA, int(budget_qps * 1.5))
        agA.tick()
        agB.tick()  # demand now reported; targets still old
        skew_rounds = 0
        while skew_rounds < 6:
            coord.reconcile_once()
            agA.tick()
            agB.tick()
            skew_rounds += 1
            sA, sB = _shares()
            if sA >= 2 * sB:
                break
        skew = {"rounds": skew_rounds, "share_a": sA, "share_b": sB}
        if sA < 2 * sB:
            failures.append(
                f"skewed demand never won the budget ({sA} vs {sB})"
            )
        elif skew_rounds > 3:
            failures.append(
                f"skew convergence took {skew_rounds} reconcile ticks "
                "(contract: <= 3)"
            )
        if sA + sB > budget_tokens:
            failures.append(
                f"post-skew shares {sA}+{sB} exceed the budget"
            )

        # phase 3 — flip to B: demand moves; count ticks from the moment
        # the coordinator re-targets (A's old demand must first drain out
        # of its sliding window — that part is window physics, not the
        # reconciler) to share convergence
        flip_rounds = converge_rounds = 0
        retargeted = False
        while flip_rounds < 40:
            _burst(clB, 60)
            agA.tick()
            agB.tick()
            coord.reconcile_once()
            flip_rounds += 1
            tg = coord.stats()["targets"].get(DRILL_FLOW, {})
            if not retargeted and (
                tg.get("pod-b", 0) > tg.get("pod-a", 0)
            ):
                retargeted = True
            elif retargeted:
                converge_rounds += 1
            sA, sB = _shares()
            if retargeted and sB >= 2 * sA:
                break
            time.sleep(bucket_ms / 1e3)
        flip = {
            "rounds_total": flip_rounds,
            "rounds_after_retarget": converge_rounds,
            "share_a": sA,
            "share_b": sB,
        }
        if not (retargeted and sB >= 2 * sA):
            failures.append(
                f"demand flip never converged ({sA} vs {sB} after "
                f"{flip_rounds} rounds)"
            )
        elif converge_rounds > 3:
            failures.append(
                f"flip convergence took {converge_rounds} ticks past "
                "the re-target (contract: <= 3)"
            )
        if sA + sB > budget_tokens:
            failures.append(f"post-flip shares {sA}+{sB} exceed the budget")

        # phase 4 — zero cross-pod hops on the decision path: with the
        # control plane quiet, a decision burst moves agent RPCs by 0
        rpc0 = (agA.stats()["agent_rpcs"] + agB.stats()["agent_rpcs"])
        decisions = _burst(clA, 150) + _burst(clB, 150)
        decision_rpcs = (
            agA.stats()["agent_rpcs"] + agB.stats()["agent_rpcs"] - rpc0
        )
        if decision_rpcs != 0:
            failures.append(
                f"{decision_rpcs} cross-pod RPCs during a decision burst "
                "(contract: the decision path never leaves the pod)"
            )

        # phase 5 — live over-admission: drain, then drive BOTH pods
        # flat-out with the control plane pacing normally. The drive stays
        # strictly INSIDE one window (window_s − 2.5 buckets): past that,
        # the drive's own front-loaded admissions age out of the sliding
        # window and legitimately refill — that is window physics, not
        # over-admission, and counting it would gate on the wrong thing.
        drive_s = window_s - 2.5 * bucket_ms / 1e3
        _drain()
        admits = 0
        t0 = time.monotonic()
        last_round = t0
        while time.monotonic() - t0 < drive_s:
            admits += _burst(clA, 25) + _burst(clB, 25)
            if time.monotonic() - last_round >= reconcile_ms / 1e3:
                _round()
                last_round = time.monotonic()
        over_live = max(0, admits - budget_tokens)
        live = {"admits": admits, "over_admission": over_live,
                "slack_tokens": slack_tokens}
        if over_live > slack_tokens:
            failures.append(
                f"live over-admission {over_live} exceeds one reconcile "
                f"interval's worth ({slack_tokens} tokens)"
            )

        # phase 6 — seeded chaos cut mid-tick: the agent must neither
        # raise nor lose its share when the renew channel is severed
        sA0, sB0 = _shares()
        chaos.arm("conn_reset:n=1", seed=chaos_seed)
        try:
            agB.tick()
        except Exception as e:
            failures.append(f"agent tick raised under chaos: {e!r}")
        finally:
            chaos.disarm()
        if agB.shares().get(DRILL_FLOW, 0) != sB0:
            failures.append("chaos-cut tick lost the agent's share")

        # phase 7 — coordinator dark: detach it; agents degrade to the
        # last-granted share, and a dark flat-out window stays bounded by
        # Σ outstanding shares (+ the same rotation slack)
        svcA.hierarchy = None
        for _ in range(3):
            agA.tick()
            agB.tick()
        sA, sB = _shares()
        if (sA, sB) != (sA0, sB0):
            failures.append(
                f"dark pods moved their shares {sA0}/{sB0} -> {sA}/{sB} "
                "(contract: hold the last grant)"
            )
        if not (agA.stats()["agent_degraded"]
                and agB.stats()["agent_degraded"]):
            failures.append("dark agents never flagged degraded mode")
        _drain(rounds_dark=True)
        admits_dark = 0
        t0 = time.monotonic()
        last_round = t0
        while time.monotonic() - t0 < drive_s:
            admits_dark += _burst(clA, 25) + _burst(clB, 25)
            if time.monotonic() - last_round >= reconcile_ms / 1e3:
                agA.tick()
                agB.tick()
                last_round = time.monotonic()
        over_dark = max(0, admits_dark - (sA + sB))
        dark = {"admits": admits_dark, "share_sum": sA + sB,
                "over_admission": over_dark}
        if over_dark > slack_tokens:
            failures.append(
                f"dark over-admission {over_dark} exceeds the outstanding-"
                f"share bound {sA + sB} + {slack_tokens} slack"
            )

        # recovery: re-attach, one round, the ledger sees both pods again
        svcA.attach_hierarchy(coord)
        _round()
        if coord.stats()["outstanding_shares"] < 2:
            failures.append("coordinator never re-leased after recovery")

        # observability: the hier series must be on the scrape surface
        if srvA.metrics_port:
            try:
                hier_series_live = (
                    "sentinel_hier_share_tokens" in _scrape(srvA.metrics_port)
                )
            except Exception as e:
                failures.append(f"hier metrics scrape failed: {e!r}")
            if not hier_series_live:
                failures.append(
                    "sentinel_hier_share_tokens missing from /metrics"
                )
    finally:
        clA.close()
        clB.close()
        agA.close()
        agB.close()
        coord.stop()
        srvA.stop()
        srvB.stop()
    return {
        "budget_tokens": budget_tokens,
        "reconcile_ms": reconcile_ms,
        "slack_tokens": slack_tokens,
        "bootstrap": bootstrap,
        "skew": skew,
        "flip": flip,
        "decision_rpcs": decision_rpcs,
        "live": live,
        "dark": dark,
        "hier_series_live": hier_series_live,
        "coordinator": {
            k: v for k, v in coord.stats().items()
            if not isinstance(v, dict)
        },
        "failures": failures,
    }


def run_push_drill(
    budget_qps: float = 200.0,
    bucket_ms: int = 200,
    lease_ttl_ms: float = 4000.0,
    lease_want: int = 40,
    flip_retry_ms: int = 1500,
    dark_ttl_ms: float = 1500.0,
    dark_want: int = 20,
):
    """Rev-7 push-plane drill: unsolicited server→client frames must cut
    client-local admission over in RTTs, not lease TTLs — and with the
    push plane dark, every TTL-era bound must still hold.

    In-process (one pod, real TCP front door) so emit→apply latency is
    measured exactly. An RTT baseline is taken first, then:

    - **breaker flip**: a pushed OPEN stops a leased client's local
      admits within ``max(10×RTT, 25ms)`` (the floor absorbs co-located
      scheduler jitter) and far inside ``0.5× lease TTL``; the local
      answers are DEGRADED with a live retry clock; a pushed CLOSED
      lifts the clock so traffic reaches the server again.
    - **lease revoke**: a pushed revoke drops the cached lease inside
      the same bound; the local admits that land between emit and apply
      stay below the TTL-era Σ-outstanding bound (the remaining slice),
      and the client degrades to wire verdicts without raising.
    - **rule epoch**: a live ``load_rules`` reaches connected clients as
      RULE_EPOCH_INVALIDATE.
    - **observability**: push frame totals, the revocation counter, and
      the emit→apply staleness histogram are populated on both the stats
      snapshot and the Prometheus scrape surface.
    - **push dark**: the same server-side events under ``push=False``
      send nothing, and the client behaves exactly as the TTL era
      promised — no pushed DEGRADED answers, local admits bounded by the
      outstanding slice, resync within the lease TTL.
    """
    from sentinel_tpu.cluster.client import TokenClient
    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig, TokenStatus
    from sentinel_tpu.engine.rules import ThresholdMode
    from sentinel_tpu.metrics.server import server_metrics

    failures = []
    cfg = EngineConfig(
        max_flows=64, max_namespaces=4, batch_size=64, bucket_ms=bucket_ms
    )
    rules = [
        ClusterFlowRule(DRILL_FLOW, budget_qps, ThresholdMode.GLOBAL),
        ClusterFlowRule(WARM_FLOW, 1e9, ThresholdMode.GLOBAL),
    ]
    svc = DefaultTokenService(cfg, lease_ttl_ms=int(lease_ttl_ms))
    svc.load_rules(rules)
    server = TokenServer(svc, port=0, metrics_port=0)
    server.start()
    wire = TokenClient("127.0.0.1", server.port, timeout_ms=500)
    leaser = TokenClient("127.0.0.1", server.port, timeout_ms=500,
                         lease=True, lease_want=lease_want)
    leaser2 = srv2 = wire2 = darkc = None
    rtt_ms = None
    flip = revoke = dark = {}
    rule_epoch_applied = False
    staleness = {}
    scrape_ok = None
    try:
        # warm the jit paths (decide + lease grant) on the unbounded flow
        warm_deadline = time.monotonic() + 60.0
        while time.monotonic() < warm_deadline:
            r = wire.request_token(WARM_FLOW)
            if r is not None and r.ok:
                break
        else:
            failures.append("server never served the warm flow")
        warm_lease = TokenClient("127.0.0.1", server.port, timeout_ms=500,
                                 lease=True, lease_want=8)
        try:
            warm_lease.request_token(WARM_FLOW)
        finally:
            warm_lease.close()

        # RTT baseline: the unit every push-cutover gate is denominated in
        samples = []
        for _ in range(50):
            t = time.monotonic()
            wire.request_token(WARM_FLOW)
            samples.append((time.monotonic() - t) * 1000.0)
        samples.sort()
        rtt_ms = round(samples[len(samples) // 2], 3)
        cut_bound_ms = max(10.0 * rtt_ms, 25.0)

        # phase 1 — breaker flip: lease first, then flip OPEN by push
        grant_deadline = time.monotonic() + 5.0
        while time.monotonic() < grant_deadline:
            leaser.request_token(DRILL_FLOW)
            if leaser.lease_stats().get("granted", 0) >= 1:
                break
            time.sleep(0.01)
        if leaser.lease_stats().get("granted", 0) < 1:
            failures.append("leased client never got a lease to flip")
        conn_deadline = time.monotonic() + 3.0
        while (server.push_hub.connections() < 1
               and time.monotonic() < conn_deadline):
            time.sleep(0.01)
        t_flip = time.monotonic()
        server.push_hub.push_breaker_flip(DRILL_FLOW, 1, flip_retry_ms)
        stop_ms = None
        degraded_wait_ms = 0
        while time.monotonic() < t_flip + 2.0:
            r = leaser.request_token(DRILL_FLOW)
            if r is not None and r.status == TokenStatus.DEGRADED:
                stop_ms = round((time.monotonic() - t_flip) * 1000.0, 3)
                degraded_wait_ms = r.wait_ms
                break
        flip = {"stop_ms": stop_ms, "bound_ms": round(cut_bound_ms, 3),
                "retry_left_ms": degraded_wait_ms}
        if stop_ms is None:
            failures.append(
                "pushed breaker OPEN never degraded the leased client"
            )
        else:
            if stop_ms > cut_bound_ms:
                failures.append(
                    f"breaker cutover took {stop_ms}ms, above the "
                    f"10xRTT bound of {cut_bound_ms:.1f}ms"
                )
            if stop_ms >= 0.5 * lease_ttl_ms:
                failures.append(
                    f"breaker cutover {stop_ms}ms is not well inside "
                    f"half the {lease_ttl_ms:.0f}ms lease TTL"
                )
            if degraded_wait_ms <= 0:
                failures.append(
                    "pushed-OPEN DEGRADED answer carried no retry clock"
                )
        if leaser.push_stats().get("breaker_flip", 0) < 1:
            failures.append("client never counted the breaker-flip push")

        # a pushed CLOSED must lift the local clock again
        server.push_hub.push_breaker_flip(DRILL_FLOW, 0, 0)
        lifted = False
        lift_deadline = time.monotonic() + 2.0
        while time.monotonic() < lift_deadline:
            r = leaser.request_token(DRILL_FLOW)
            if r is not None and r.status != TokenStatus.DEGRADED:
                lifted = True
                break
            time.sleep(0.005)
        flip["lifted"] = lifted
        if not lifted:
            failures.append("pushed CLOSED never lifted the breaker clock")

        # phase 2 — lease revoke: a fresh leased client (no flip backoff),
        # slice partially spent, then revoked by push. The drive is paced
        # at ~1ms (a realistic per-request cadence) so the admits that
        # land before the apply measure the cutover, not loop speed.
        leaser2 = TokenClient("127.0.0.1", server.port, timeout_ms=500,
                              lease=True, lease_want=lease_want)
        spent = 0
        for _ in range(5):
            r = leaser2.request_token(DRILL_FLOW)
            if r is not None and r.ok:
                spent += 1
        if leaser2.lease_stats().get("granted", 0) < 1:
            failures.append("revoke-phase client never got a lease")
        remaining_slice = lease_want - spent
        la0 = leaser2.lease_stats().get("local_admits", 0)
        t_rev = time.monotonic()
        server.push_hub.push_lease_revoke(0, DRILL_FLOW)  # 0 = any lease
        revoke_ms = None
        while time.monotonic() < t_rev + 2.0:
            if leaser2.lease_stats().get("revoked", 0) >= 1:
                revoke_ms = round((time.monotonic() - t_rev) * 1000.0, 3)
                break
            leaser2.request_token(DRILL_FLOW)
            time.sleep(0.001)
        local_after = leaser2.lease_stats().get("local_admits", 0) - la0
        revoke = {"stop_ms": revoke_ms, "local_admits_after": local_after,
                  "ttl_era_bound": remaining_slice}
        if revoke_ms is None:
            failures.append("pushed revoke never dropped the cached lease")
        elif revoke_ms > cut_bound_ms:
            failures.append(
                f"revoke cutover took {revoke_ms}ms, above the 10xRTT "
                f"bound of {cut_bound_ms:.1f}ms"
            )
        if local_after >= remaining_slice:
            failures.append(
                f"{local_after} local admits landed after the revoke "
                f"push — not below the TTL-era slice bound of "
                f"{remaining_slice}"
            )
        r = leaser2.request_token(DRILL_FLOW)
        if r is None or r.status == TokenStatus.FAIL:
            failures.append(
                "revoked client did not degrade to wire verdicts"
            )

        # phase 3 — rule epoch: a live reload reaches connected clients
        re0 = wire.push_stats().get("rule_epoch_invalidate", 0)
        svc.load_rules(rules)
        epoch_deadline = time.monotonic() + 2.0
        while time.monotonic() < epoch_deadline:
            if wire.push_stats().get("rule_epoch_invalidate", 0) > re0:
                rule_epoch_applied = True
                break
            time.sleep(0.01)
        if not rule_epoch_applied:
            failures.append(
                "rule reload never reached the client as an epoch push"
            )

        # phase 4 — observability: the emit→apply staleness histogram and
        # the frame/revocation counters must be populated
        snap = server_metrics().snapshot().get("push") or {}
        staleness = dict(snap.get("stalenessMs") or {})
        if not staleness.get("count"):
            failures.append("push staleness histogram is empty")
        if not snap.get("frames"):
            failures.append("push frame totals are empty")
        if snap.get("revocations", 0) < 1:
            failures.append("push revocation counter never moved")
        if server.metrics_port:
            try:
                body = _scrape(server.metrics_port)
                scrape_ok = all(
                    needle in body
                    for needle in ("sentinel_push_frames_total",
                                   "sentinel_push_staleness_ms")
                )
            except Exception as e:
                failures.append(f"push metrics scrape failed: {e!r}")
            if scrape_ok is False:
                failures.append("push series missing from /metrics")

        # phase 5 — push dark: same events, push=False server. Nothing is
        # sent, nothing is locally DEGRADED, and the client resyncs on
        # the TTL-era machinery (renew-ahead / expiry) with local admits
        # bounded by the outstanding slice.
        svc2 = DefaultTokenService(cfg, lease_ttl_ms=int(dark_ttl_ms))
        svc2.load_rules(rules)
        srv2 = TokenServer(svc2, port=0, metrics_port=0, push=False)
        srv2.start()
        wire2 = TokenClient("127.0.0.1", srv2.port, timeout_ms=500)
        warm_deadline = time.monotonic() + 60.0
        while time.monotonic() < warm_deadline:
            r = wire2.request_token(WARM_FLOW)
            if r is not None and r.ok:
                break
        else:
            failures.append("dark server never served the warm flow")
        darkc = TokenClient("127.0.0.1", srv2.port, timeout_ms=500,
                            lease=True, lease_want=dark_want)
        dark_spent = 0
        for _ in range(3):
            r = darkc.request_token(DRILL_FLOW)
            if r is not None and r.ok:
                dark_spent += 1
        if darkc.lease_stats().get("granted", 0) < 1:
            failures.append("dark-phase client never got a lease")
        # server-side breaker flip AND lease revoke, both with the push
        # plane disarmed: the flip emit is a no-op, the sweep reclaims
        # the charge server-side but nothing tells the client
        srv2.push_hub.push_breaker_flip(DRILL_FLOW, 1, 60_000)
        with svc2._lock:
            for lease in svc2._leases.values():
                lease.expiry_ms = 0
            svc2._sweep_leases_locked(now=1)
        st0 = darkc.lease_stats()
        base = {k: st0.get(k, 0) for k in ("granted", "renewed", "expired")}
        la0 = st0.get("local_admits", 0)
        t_dark = time.monotonic()
        resync_ms = None
        degraded_seen = False
        dark_deadline = t_dark + dark_ttl_ms / 1000.0 + 2.5
        while time.monotonic() < dark_deadline:
            st = darkc.lease_stats()
            # TTL-era resync machinery, whichever fires first: the
            # renew-ahead (carries the dead lease id, degrades to a fresh
            # server-accounted grant) or client-side expiry
            if any(st.get(k, 0) > base[k]
                   for k in ("granted", "renewed", "expired")):
                resync_ms = round((time.monotonic() - t_dark) * 1000.0, 1)
                break
            r = darkc.request_token(DRILL_FLOW)
            if r is not None and r.status == TokenStatus.DEGRADED:
                degraded_seen = True
            time.sleep(0.002)
        dark_local = darkc.lease_stats().get("local_admits", 0) - la0
        hub2 = srv2.push_hub.stats()
        dark = {
            "resync_ms": resync_ms,
            "local_admits_after_revoke": dark_local,
            "slice_bound": dark_want - dark_spent,
            "hub_sent": hub2.get("sent"),
        }
        if degraded_seen:
            failures.append(
                "push-dark client answered DEGRADED with no push applied"
            )
        if resync_ms is None:
            failures.append(
                "push-dark client never resynced inside the lease TTL"
            )
        if dark_local > (dark_want - dark_spent) + 2:
            failures.append(
                f"push-dark local admits {dark_local} exceed the "
                f"outstanding-slice bound {dark_want - dark_spent}"
            )
        if hub2.get("enabled") or hub2.get("sent"):
            failures.append("push=False server still sent push frames")
        dc = darkc.push_stats()
        if any(dc.get(k, 0) for k in ("lease_revoke", "breaker_flip",
                                      "rule_epoch_invalidate")):
            failures.append("push-dark client counted applied pushes")
    finally:
        for c in (wire, leaser, leaser2, wire2, darkc):
            if c is not None:
                try:
                    c.close()
                except Exception:
                    pass
        server.stop()
        if srv2 is not None:
            srv2.stop()
    return {
        "rtt_ms": rtt_ms,
        "lease_ttl_ms": lease_ttl_ms,
        "flip": flip,
        "revoke": revoke,
        "rule_epoch_applied": rule_epoch_applied,
        "stalenessMs": staleness,
        "scrape_ok": scrape_ok,
        "dark": dark,
        "failures": failures,
    }


def run_election_drill(
    budget_qps: float = 200.0,
    bucket_ms: int = 100,
    lock_ttl_ms: int = 1200,
    reconcile_ms: float = 100.0,
):
    """Coordinator auto-election drill: the global tier has NO configured
    single point — no pod is told who hosts the coordinator.

    Two live pods each run a :class:`CoordinatorElection` against a
    shared shard-map publisher. Agents learn the coordinator endpoint
    from the map's ``global_flows`` section (never from config), a
    connected client witnesses the SHARD_MAP_PUSH that broadcasts each
    election outcome, and the drill then crashes the leader
    (``hard_stop`` — lock NOT released, the SIGKILL shape) and gates:

    - exactly one winner per election round, arbitrated by the epoch
      fence alone;
    - admissions during the leaderless window stay within Σ outstanding
      shares + one reconcile interval's slack (≤ the global budget);
    - the survivor claims after the lock TTL lapses and the new ledger
      re-covers both pods within ≤ 3 reconcile ticks of the win;
    - the new leader's map and push name its endpoint, and the agents'
      renews (unknown share ids to the empty ledger) degrade to grants
      with no handshake.
    """
    from sentinel_tpu.cluster.client import TokenClient
    from sentinel_tpu.cluster.hierarchy import (
        COORD_LOCK_KEY,
        CoordinatorElection,
        GlobalFlowBudget,
        PodShareAgent,
        decode_coord_lock,
    )
    from sentinel_tpu.cluster.rebalance import (
        ShardMapPublisher,
        decode_shard_map_doc,
    )
    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
    from sentinel_tpu.engine.rules import ThresholdMode

    failures = []
    window_s = bucket_ms * 10 / 1000.0
    budget_tokens = int(budget_qps * window_s)
    slack_tokens = max(2, int(budget_tokens * reconcile_ms / (window_s * 1e3)))
    cfg = EngineConfig(
        max_flows=64, max_namespaces=4, batch_size=64, bucket_ms=bucket_ms
    )
    svcA = DefaultTokenService(cfg)
    svcB = DefaultTokenService(cfg)
    for svc in (svcA, svcB):
        svc.load_rules(
            [ClusterFlowRule(DRILL_FLOW, budget_qps, ThresholdMode.GLOBAL),
             ClusterFlowRule(WARM_FLOW, 1e9, ThresholdMode.GLOBAL)]
        )
    srvA = TokenServer(svcA, port=0, metrics_port=0)
    srvB = TokenServer(svcB, port=0, metrics_port=0)
    srvA.start()
    srvB.start()
    epA = f"127.0.0.1:{srvA.port}"
    epB = f"127.0.0.1:{srvB.port}"
    pub = ShardMapPublisher()
    budgets = [GlobalFlowBudget(DRILL_FLOW, budget_qps, window_s)]
    hubs = (srvA.push_hub, srvB.push_hub)
    eA = CoordinatorElection(
        svcA, pub, "pod-a", epA, budgets, lock_ttl_ms=lock_ttl_ms,
        share_ttl_ms=30_000, reconcile_ms=reconcile_ms, push_hubs=hubs,
    )
    eB = CoordinatorElection(
        svcB, pub, "pod-b", epB, budgets, lock_ttl_ms=lock_ttl_ms,
        share_ttl_ms=30_000, reconcile_ms=reconcile_ms, push_hubs=hubs,
    )
    clA = TokenClient("127.0.0.1", srvA.port, timeout_ms=500)
    clB = TokenClient("127.0.0.1", srvB.port, timeout_ms=500)
    witness = TokenClient("127.0.0.1", srvB.port, timeout_ms=500)
    seen_maps = []

    def _witness_learn(blob):
        try:
            m = decode_shard_map_doc(blob)
        except ValueError:
            return
        seen_maps.append((int(m.epoch), dict(m.global_flows)))

    witness.on_shard_map = _witness_learn
    agA = agB = None
    subs = []
    election = {}
    dark = {}
    failover = {}
    push_named_leader = push_named_survivor = False

    def _burst(cl, n, fid=DRILL_FLOW):
        ok = 0
        for _ in range(n):
            r = cl.request_token(fid)
            if r is not None and r.ok:
                ok += 1
        return ok

    try:
        # warm every decide kernel BEFORE any hold is pinned: the first
        # decide per service pays its jit trace, which would otherwise
        # age a fresh hold out of the window mid-measurement
        warm_deadline = time.monotonic() + 60.0
        while time.monotonic() < warm_deadline:
            if (_burst(clA, 1, WARM_FLOW) and _burst(clB, 1, WARM_FLOW)
                    and _burst(witness, 1, WARM_FLOW)):
                break
        else:
            failures.append("pods never served the warm flow")

        # phase 1 — first election: exactly one winner, map names it
        ledA = eA.tick()
        ledB = eB.tick()
        if int(ledA) + int(ledB) != 1:
            failures.append(
                f"expected exactly one election winner, got "
                f"{int(ledA) + int(ledB)}"
            )
        leader, standby = (eA, eB) if ledA else (eB, eA)
        m = pub.current()
        learned_ep = (m.global_flows or {}).get(str(DRILL_FLOW))
        election = {"winner": leader.pod_id, "epoch": int(m.epoch),
                    "learned_endpoint": learned_ep}
        if learned_ep != leader.endpoint:
            failures.append(
                f"map points {learned_ep!r} at the flow, leader is "
                f"{leader.endpoint!r}"
            )
        if decode_coord_lock(
            (m.global_flows or {}).get(COORD_LOCK_KEY)
        ) is None:
            failures.append("no live coordinator lock in the map")
        push_deadline = time.monotonic() + 2.0
        while time.monotonic() < push_deadline:
            if any(gf.get(str(DRILL_FLOW)) == leader.endpoint
                   for _, gf in seen_maps):
                push_named_leader = True
                break
            time.sleep(0.01)
        if not push_named_leader:
            failures.append(
                "election outcome never reached the witness by push"
            )

        # phase 2 — agents bootstrap from the LEARNED endpoint (nothing
        # is configured) and follow future maps through the publisher
        agA = PodShareAgent(svcA, [learned_ep], "pod-a", [DRILL_FLOW],
                            tick_ms=100)
        agB = PodShareAgent(svcB, [learned_ep], "pod-b", [DRILL_FLOW],
                            tick_ms=100)
        for ag in (agA, agB):
            subs.append(pub.listen(
                lambda mp, ag=ag: (
                    ag.apply_shard_map(mp) if mp is not None else None
                )
            ))
        for _ in range(2):
            agA.tick()
            agB.tick()
            if leader.coordinator is not None:
                leader.coordinator.reconcile_once()
            eA.tick()
            eB.tick()
        sA0 = agA.shares().get(DRILL_FLOW, 0)
        sB0 = agB.shares().get(DRILL_FLOW, 0)
        election["share_a"] = sA0
        election["share_b"] = sB0
        if sA0 + sB0 > budget_tokens:
            failures.append(
                f"bootstrap shares {sA0}+{sB0} exceed the budget "
                f"{budget_tokens}"
            )
        if not (sA0 and sB0):
            failures.append(f"bootstrap split {sA0}/{sB0} left a pod dry")

        # phase 3 — SIGKILL shape: the leader vanishes without releasing
        # the lock; its pod stops hosting the coordinator function
        t_kill = time.monotonic()
        leader.hard_stop()
        leader.service.hierarchy = None

        # leaderless drive, strictly inside one window: admissions stay
        # within Σ outstanding shares + one reconcile interval's slack
        drive_s = window_s - 2.5 * bucket_ms / 1e3
        admits_dark = 0
        t0 = time.monotonic()
        last = t0
        while time.monotonic() - t0 < drive_s:
            admits_dark += _burst(clA, 20) + _burst(clB, 20)
            if time.monotonic() - last >= reconcile_ms / 1e3:
                agA.tick()
                agB.tick()
                last = time.monotonic()
        over_dark = max(0, admits_dark - (sA0 + sB0))
        dark = {"admits": admits_dark, "share_sum": sA0 + sB0,
                "over_admission": over_dark, "slack_tokens": slack_tokens}
        if over_dark > slack_tokens:
            failures.append(
                f"leaderless over-admission {over_dark} exceeds the "
                f"outstanding-share bound {sA0 + sB0} + {slack_tokens}"
            )

        # phase 4 — the survivor waits out the lock TTL and claims
        won_ms = None
        wait_deadline = t_kill + lock_ttl_ms / 1e3 + 3.0
        while time.monotonic() < wait_deadline:
            if standby.tick():
                won_ms = round((time.monotonic() - t_kill) * 1000.0, 1)
                break
            agA.tick()
            agB.tick()
            time.sleep(0.05)
        if won_ms is None:
            failures.append(
                "survivor never won the election after the crash"
            )

        # convergence: ≤ 3 reconcile ticks from the win to a ledger that
        # re-covers both pods (renews with unknown share ids degrade to
        # plain grants — no handshake)
        conv_rounds = 0
        converged = False
        newc = standby.coordinator
        while newc is not None and conv_rounds < 6:
            agA.tick()
            agB.tick()
            newc.reconcile_once()
            standby.tick()
            conv_rounds += 1
            if newc.stats().get("outstanding_shares", 0) >= 2:
                converged = True
                break
        sA1 = agA.shares().get(DRILL_FLOW, 0)
        sB1 = agB.shares().get(DRILL_FLOW, 0)
        m2 = pub.current()
        failover = {
            "won_ms": won_ms, "rounds_to_converge": conv_rounds,
            "share_a": sA1, "share_b": sB1,
            "learned_endpoint": (m2.global_flows or {}).get(
                str(DRILL_FLOW)
            ),
            "survivor": standby.pod_id,
        }
        if not converged:
            failures.append(
                "new coordinator never re-covered both pods "
                f"({conv_rounds} rounds)"
            )
        elif conv_rounds > 3:
            failures.append(
                f"auto-election convergence took {conv_rounds} reconcile "
                "ticks (contract: <= 3)"
            )
        if sA1 + sB1 > budget_tokens:
            failures.append(
                f"post-failover shares {sA1}+{sB1} exceed the budget"
            )
        if not (sA1 and sB1):
            failures.append("a pod holds no share after the failover")
        if failover["learned_endpoint"] != standby.endpoint:
            failures.append(
                "the map does not name the survivor as coordinator"
            )
        push_deadline = time.monotonic() + 2.0
        while time.monotonic() < push_deadline:
            if any(gf.get(str(DRILL_FLOW)) == standby.endpoint
                   for _, gf in seen_maps):
                push_named_survivor = True
                break
            time.sleep(0.01)
        if not push_named_survivor:
            failures.append(
                "failover outcome never reached the witness by push"
            )
        if standby.stats().get("elections_won", 0) != 1:
            failures.append("survivor won more than one election")
    finally:
        for c in (clA, clB, witness):
            try:
                c.close()
            except Exception:
                pass
        for ag in (agA, agB):
            if ag is not None:
                try:
                    ag.close()
                except Exception:
                    pass
        for e in (eA, eB):
            try:
                e.stop(release=False)
            except Exception:
                pass
        srvA.stop()
        srvB.stop()
    return {
        "budget_tokens": budget_tokens,
        "lock_ttl_ms": lock_ttl_ms,
        "configured_coordinator_endpoints": [],
        "election": election,
        "dark": dark,
        "failover": failover,
        "push_named_leader": push_named_leader,
        "push_named_survivor": push_named_survivor,
        "maps_witnessed": len(seen_maps),
        "failures": failures,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--serve", action="store_true",
                    help="internal: run one server child")
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--skip-overload", action="store_true",
                    help="run only the kill/failover phases")
    ap.add_argument("--skip-replication", action="store_true",
                    help="skip the warm-standby replication drill")
    ap.add_argument("--skip-rebalance", action="store_true",
                    help="skip the live shard-rebalance drill")
    ap.add_argument("--skip-lease", action="store_true",
                    help="skip the kill-with-leases-outstanding drill")
    ap.add_argument("--only-lease", action="store_true",
                    help="run ONLY the lease drill (the CI lease-smoke "
                         "job's fast path)")
    ap.add_argument("--skip-hier", action="store_true",
                    help="skip the two-pod hierarchical-limit drill")
    ap.add_argument("--only-hier", action="store_true",
                    help="run ONLY the hierarchical-limit drill (the CI "
                         "hier-smoke job's fast path)")
    ap.add_argument("--hier-seed", type=int, default=7,
                    help="chaos seed for the hier drill's conn_reset cut")
    ap.add_argument("--skip-push", action="store_true",
                    help="skip the rev-7 push-plane drill")
    ap.add_argument("--only-push", action="store_true",
                    help="run ONLY the push-plane + auto-election drills "
                         "(the CI push-smoke job's fast path)")
    ap.add_argument("--skip-election", action="store_true",
                    help="skip the coordinator auto-election drill")
    # child-role flags (used with --serve)
    ap.add_argument("--standby-of", default=None)
    ap.add_argument("--promote-after-ms", type=float, default=None)
    ap.add_argument("--replicate-to", default=None)
    ap.add_argument("--repl-interval-ms", type=float, default=None)
    ap.add_argument("--count", type=float, default=1e9)
    ap.add_argument("--bucket-ms", type=int, default=100)
    ap.add_argument("--lease-ttl-ms", type=float, default=500.0)
    args = ap.parse_args()
    if args.serve:
        _serve_forever(args)
        return
    import jax

    jax.config.update("jax_platforms", "cpu")
    t0 = time.time()
    if args.only_lease:
        doc = {"lease": run_lease_drill()}
        doc["failures"] = doc["lease"]["failures"]
        doc["wall_s"] = round(time.time() - t0, 1)
        print(json.dumps(doc, indent=2))
        if doc["failures"]:
            print(f"LEASE DRILL FAILED: {doc['failures']}", file=sys.stderr)
            sys.exit(1)
        lease = doc["lease"]
        print(
            f"lease drill ok: over-admitted {lease['over_admission']} of "
            f"{lease['window_tokens']:.0f} window tokens against an "
            f"outstanding-lease bound of "
            f"{lease['outstanding_tokens_at_kill']} "
            f"({lease['local_admits']} client-local admits survived the "
            f"kill, standby blocked {lease['standby_blocks']}x)"
        )
        return
    if args.only_push:
        doc = {"push": run_push_drill(),
               "election": run_election_drill()}
        doc["failures"] = (
            doc["push"]["failures"] + doc["election"]["failures"]
        )
        doc["wall_s"] = round(time.time() - t0, 1)
        print(json.dumps(doc, indent=2))
        if doc["failures"]:
            print(f"PUSH DRILL FAILED: {doc['failures']}", file=sys.stderr)
            sys.exit(1)
        push = doc["push"]
        elec = doc["election"]
        print(
            f"push drill ok: breaker cutover {push['flip']['stop_ms']}ms "
            f"and revoke cutover {push['revoke']['stop_ms']}ms against a "
            f"{push['flip']['bound_ms']}ms 10xRTT bound "
            f"(RTT {push['rtt_ms']}ms, lease TTL "
            f"{push['lease_ttl_ms']:.0f}ms); dark resync "
            f"{push['dark']['resync_ms']}ms; election failover converged "
            f"in {elec['failover']['rounds_to_converge']} tick(s) "
            f"({elec['failover']['won_ms']}ms past the kill), leaderless "
            f"over-admission {elec['dark']['over_admission']}"
        )
        return
    if args.only_hier:
        doc = {"hier": run_hier_drill(chaos_seed=args.hier_seed)}
        doc["failures"] = doc["hier"]["failures"]
        doc["wall_s"] = round(time.time() - t0, 1)
        print(json.dumps(doc, indent=2))
        if doc["failures"]:
            print(f"HIER DRILL FAILED: {doc['failures']}", file=sys.stderr)
            sys.exit(1)
        hier = doc["hier"]
        print(
            f"hier drill ok: skew converged in {hier['skew']['rounds']} "
            f"tick(s), flip in {hier['flip']['rounds_after_retarget']} "
            f"tick(s) past re-target, {hier['decision_rpcs']} cross-pod "
            f"RPCs per decision burst, live over-admission "
            f"{hier['live']['over_admission']} of "
            f"{hier['budget_tokens']} (slack {hier['slack_tokens']}), "
            f"dark over-admission {hier['dark']['over_admission']}"
        )
        return
    doc = run_drill(deadline_ms=args.deadline_ms)
    from sentinel_tpu.metrics.exporter import build_info

    doc["build"] = build_info()
    if not args.skip_replication:
        doc["replication"] = run_replication_drill()
        doc["failures"] = doc["failures"] + doc["replication"]["failures"]
    if not args.skip_rebalance:
        doc["rebalance"] = run_rebalance_drill()
        doc["failures"] = doc["failures"] + doc["rebalance"]["failures"]
    if not args.skip_lease:
        doc["lease"] = run_lease_drill()
        doc["failures"] = doc["failures"] + doc["lease"]["failures"]
    if not args.skip_hier:
        doc["hier"] = run_hier_drill(chaos_seed=args.hier_seed)
        doc["failures"] = doc["failures"] + doc["hier"]["failures"]
    if not args.skip_push:
        doc["push"] = run_push_drill()
        doc["failures"] = doc["failures"] + doc["push"]["failures"]
    if not args.skip_election:
        doc["election"] = run_election_drill()
        doc["failures"] = doc["failures"] + doc["election"]["failures"]
    if not args.skip_overload:
        doc["overload"] = run_overload_drill()
        doc["failures"] = doc["failures"] + doc["overload"]["failures"]
    doc["wall_s"] = round(time.time() - t0, 1)
    print(json.dumps(doc, indent=2))
    if doc["failures"]:
        print(f"HA DRILL FAILED: {doc['failures']}", file=sys.stderr)
        sys.exit(1)
    print(
        f"ha drill ok: converged in {doc['failover_convergence_ms']}ms "
        f"(deadline {doc['deadline_ms']:.0f}ms), "
        f"{doc['fallback_requests']} all-down requests resolved "
        f"(blocked rate {doc['fallback_blocked_rate']:.2f})"
    )
    if "replication" in doc:
        rep = doc["replication"]
        print(
            f"replication drill ok: over-admitted {rep['over_admission']} "
            f"of {rep['window_tokens']:.0f} window tokens "
            f"(budget {rep['staleness_budget']}), standby promoted and "
            f"served in {rep['promote_convergence_ms']}ms, "
            f"{rep['standby_blocks']} post-promotion blocks, "
            f"repl lag gauge live={rep['repl_lag_gauge_live']}, "
            f"param delta bytes slim {rep['param_delta_bytes']['slim']}B "
            f"vs fat {rep['param_delta_bytes']['fat']}B "
            f"({rep['param_delta_bytes']['ratio']}x)"
        )
    if "rebalance" in doc:
        reb = doc["rebalance"]
        print(
            f"rebalance drill ok: over-admitted {reb['over_admission']} "
            f"of {reb['window_tokens']:.0f} window tokens across the move "
            f"({reb['admitted']} admitted, {reb['blocked']} blocked, "
            f"{reb['requests_raised']} raised), abort atomic="
            f"{reb['abort_atomic']}, live move {reb['move_wall_ms']}ms, "
            f"{reb['epochs_crossed']} epoch(s) crossed"
        )
    if "lease" in doc:
        lease = doc["lease"]
        print(
            f"lease drill ok: over-admitted {lease['over_admission']} of "
            f"{lease['window_tokens']:.0f} window tokens against an "
            f"outstanding-lease bound of "
            f"{lease['outstanding_tokens_at_kill']} "
            f"({lease['local_admits']} client-local admits survived the "
            f"kill, standby blocked {lease['standby_blocks']}x)"
        )
    if "hier" in doc:
        hier = doc["hier"]
        print(
            f"hier drill ok: skew converged in {hier['skew']['rounds']} "
            f"tick(s), flip in {hier['flip']['rounds_after_retarget']} "
            f"tick(s) past re-target, {hier['decision_rpcs']} cross-pod "
            f"RPCs per decision burst, live over-admission "
            f"{hier['live']['over_admission']} of "
            f"{hier['budget_tokens']} (slack {hier['slack_tokens']}), "
            f"dark over-admission {hier['dark']['over_admission']}"
        )
    if "push" in doc:
        push = doc["push"]
        print(
            f"push drill ok: breaker cutover {push['flip']['stop_ms']}ms "
            f"and revoke cutover {push['revoke']['stop_ms']}ms against a "
            f"{push['flip']['bound_ms']}ms 10xRTT bound "
            f"(RTT {push['rtt_ms']}ms), dark resync "
            f"{push['dark']['resync_ms']}ms"
        )
    if "election" in doc:
        elec = doc["election"]
        print(
            f"election drill ok: {elec['failover']['survivor']} converged "
            f"in {elec['failover']['rounds_to_converge']} tick(s) "
            f"({elec['failover']['won_ms']}ms past the kill), leaderless "
            f"over-admission {elec['dark']['over_admission']} of "
            f"{elec['dark']['share_sum']} outstanding"
        )
    if "overload" in doc:
        ovl = doc["overload"]
        print(
            f"overload drill ok: {ovl['answered_frac']:.4f} answered at "
            f"{ovl['offered_rate_vps']} vps offered "
            f"({ovl['capacity_vps']} vps capacity), "
            f"shed {sum(ovl['shed_by_reason'].values())} rows "
            f"{ovl['shed_by_reason']}, p99 {ovl['p99_ms']}ms, "
            f"probe evicted={ovl['probe']['evicted']}"
        )


if __name__ == "__main__":
    main()
