"""End-to-end serve bench: TCP front door → micro-batcher → device kernel.

The one-pipeline measurement the reference gets from
``NettyTransportServer.java:73-101`` → ``TokenServerHandler.java:61`` →
``DefaultTokenService.java:39``: clients on sockets, verdicts from the
device, measured as a single system — served verdicts/s AND latency
percentiles in one artifact, on whatever backend executes the kernel.

Two phases, both driven by ``serve_client.py`` subprocess workers (which pin
jax to CPU before anything else — the device belongs to THIS process):

- **closed-loop**: pipelined clients measure the served ceiling and its
  per-frame RTT percentiles.
- **open-loop sweep**: paced clients offer fixed loads; each point reports
  achieved rate + RTT percentiles → a load-latency curve, from which the
  **operating point** is chosen: the highest achieved rate whose p99 meets
  the BASELINE.md SLO (2ms). This is the artifact that shows BOTH halves of
  the north star at ONE operating point (VERDICT r4 missing #2).

Importable (``serve_measure()``) so bench.py's child runs it as enrichment
stages on the live backend; the CLI wraps the same path for standalone runs.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
CLIENT = os.path.join(REPO, "benchmarks", "serve_client.py")
SLO_P99_MS = 2.0  # BASELINE.md north-star latency half


def _spawn_clients(argsets, timeout_s: float):
    """Run one serve_client.py subprocess per argset; return parsed docs."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # one process per chip: the device belongs to the server (this
    # process), so a client is started pinned to the CPU
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, CLIENT, *map(str, a)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            env=env,
        )
        for a in argsets
    ]
    docs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout_s)
            line = next(
                (ln for ln in reversed(out.splitlines())
                 if ln.startswith("{")), None,
            )
            docs.append(json.loads(line) if line else None)
        except subprocess.TimeoutExpired:
            p.kill()
            p.communicate()
            docs.append(None)
    return [d for d in docs if d is not None]


def _pcts(rtt_ms: np.ndarray) -> dict:
    if rtt_ms.size == 0:
        return {"p50_ms": None, "p90_ms": None, "p99_ms": None, "max_ms": None}
    return {
        "p50_ms": round(float(np.percentile(rtt_ms, 50)), 3),
        "p90_ms": round(float(np.percentile(rtt_ms, 90)), 3),
        "p99_ms": round(float(np.percentile(rtt_ms, 99)), 3),
        "max_ms": round(float(rtt_ms.max()), 3),
    }


def force_virtual_cpu_devices(n: int) -> None:
    """Pin jax to a CPU backend exposing ``n`` virtual devices (the
    ``--mesh-devices`` mode off a real pod). Must run before the first
    backend creation — same recipe as tests/conftest.py."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    got = len(jax.devices())
    if got < n:
        raise RuntimeError(
            f"wanted {n} virtual CPU devices, backend exposes {got} "
            "(jax already initialized before the flag?)"
        )


def build_server(n_flows: int = 100_000, max_batch: int = 16384,
                 serve_buckets=(4096, 16384), native: bool = True,
                 port: int = 0, n_dispatchers: int = 2,
                 fuse_depth: int = 4, intake_shards: int = 1,
                 mesh_devices: int = 0, shm_dir=None):
    """Service (100k rules — the headline's problem size) + front door.

    ``mesh_devices > 0`` backs the service with a flow-sharded mesh over
    that many devices (the caller must have made them visible — see
    :func:`force_virtual_cpu_devices` for the CPU-mesh recipe); the front
    door and everything behind it is unchanged, which is the point."""
    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
    from sentinel_tpu.engine.rules import ThresholdMode

    config = EngineConfig(
        max_flows=n_flows, max_namespaces=64, batch_size=max_batch,
    )
    mesh = None
    if mesh_devices:
        import jax

        from sentinel_tpu.parallel import make_flow_mesh

        mesh = make_flow_mesh(jax.devices()[:mesh_devices])
    service = DefaultTokenService(
        config, serve_buckets=serve_buckets, mesh=mesh
    )
    service.load_rules(
        [
            ClusterFlowRule(flow_id=i, count=1e9, mode=ThresholdMode.GLOBAL,
                            namespace=f"ns{i % 64}")
            for i in range(n_flows)
        ],
        ns_max_qps=1e12,
    )
    # compile every serve-bucket kernel variant BEFORE any client connects:
    # a first dispatch that compiles once consumed the whole closed-loop
    # measurement window (every pump thread's clock expired during its
    # warmup round trip → a 0-verdict artifact with 0 errors). A warmup
    # failure is a failure: nothing here serves cold.
    service.warmup()
    if native:
        # asked for by name: a library that cannot be built or loaded
        # raises with the compiler's output, the door is never swapped
        from sentinel_tpu.cluster.server_native import NativeTokenServer

        server = NativeTokenServer(
            service, host="127.0.0.1", port=port,
            max_batch=max_batch, n_dispatchers=n_dispatchers,
            fuse_depth=fuse_depth, intake_shards=intake_shards,
            shm_dir=shm_dir,
        )
        front_door = "native-epoll"
    else:
        server = TokenServer(service, host="127.0.0.1", port=port,
                             max_batch=max_batch, n_loops=1)
        front_door = "asyncio"
    server.start()
    return service, server, front_door


def run_closed(port: int, clients: int = 4, batch: int = 2048,
               pipeline: int = 2, seconds: float = 6.0,
               n_flows: int = 100_000, shm_dir=None) -> dict:
    transport = ("--transport", "shm", "--shm-dir", shm_dir) \
        if shm_dir else ()
    t0 = time.perf_counter()
    docs = _spawn_clients(
        [
            ("--port", port, "--mode", "closed", "--batch", batch,
             "--pipeline", pipeline, "--seconds", seconds,
             "--flows", n_flows, "--seed", k, *transport)
            for k in range(clients)
        ],
        timeout_s=seconds * 4 + 120,
    )
    wall = time.perf_counter() - t0
    ok = sum(d["verdicts_ok"] for d in docs)
    err = sum(d["verdicts_err"] for d in docs)
    rtt = np.concatenate(
        [np.asarray(d["rtt_ms"]) for d in docs if d["rtt_ms"]]
    ) if any(d["rtt_ms"] for d in docs) else np.empty(0)
    # served rate over each client's own measurement window (excludes
    # subprocess startup skew which `wall` here would include)
    client_wall = max((d["wall_s"] for d in docs), default=wall)
    return {
        "verdicts_per_sec": round(ok / client_wall) if docs else 0,
        "wall_s": round(client_wall, 3),
        "verdicts_ok": ok,
        "errors": err,
        "clients": len(docs),
        "batch_per_frame": batch,
        "pipeline_per_client": pipeline,
        "seconds": seconds,
        **_pcts(rtt),
    }


def run_sweep(port: int, rates, batch: int = 1024, seconds: float = 4.0,
              clients: int = 2, n_flows: int = 100_000,
              window: int = 32, deadline_ts: float = None) -> list:
    """Open-loop load-latency curve. Stops early once a point is hopeless
    (p99 >> SLO and shedding) or saturated (higher offered load cannot
    raise the achieved rate), so overload doesn't burn the bench budget."""
    points = []
    for rate in rates:
        if deadline_ts is not None and time.perf_counter() > deadline_ts:
            break
        docs = _spawn_clients(
            [
                ("--port", port, "--mode", "open", "--batch", batch,
                 "--rate", rate / clients, "--seconds", seconds,
                 "--flows", n_flows, "--window", window, "--seed", k)
                for k in range(clients)
            ],
            timeout_s=seconds * 4 + 120,
        )
        if not docs:
            points.append({"offered_rate": rate, "error": "clients failed"})
            break
        rtt = np.concatenate(
            [np.asarray(d["rtt_ms"]) for d in docs if d["rtt_ms"]]
        ) if any(d["rtt_ms"] for d in docs) else np.empty(0)
        sent = sum(d["frames_sent"] for d in docs)
        dropped = sum(d["frames_dropped"] for d in docs)
        lost = sum(d["frames_lost"] for d in docs)
        achieved = sum(d["achieved_send_rate"] for d in docs)
        point = {
            "offered_rate": int(rate),
            "achieved_rate": int(achieved),
            "frames_sent": sent,
            "frames_dropped": dropped,
            "frames_lost": lost,
            **_pcts(rtt),
        }
        points.append(point)
        p99 = point["p99_ms"]
        if p99 is not None and p99 > 4 * SLO_P99_MS and dropped > sent:
            break  # far past saturation; higher rates only repeat the story
        if dropped > sent and achieved < 0.5 * rate:
            break  # server saturated: higher offers only re-measure the shed
    return points


def operating_point(points) -> dict | None:
    """Highest achieved rate meeting the SLO with <1% shed/lost frames."""
    best = None
    for p in points:
        if p.get("p99_ms") is None:
            continue
        total = p["frames_sent"] + p["frames_dropped"]
        shed = (p["frames_dropped"] + p["frames_lost"]) / max(total, 1)
        if p["p99_ms"] < SLO_P99_MS and shed < 0.01:
            if best is None or p["achieved_rate"] > best["achieved_rate"]:
                best = p
    return best


def measure_lease(port: int, n_flows: int = 100_000, seconds: float = 4.0,
                  seed: int = 0, alpha: float = 1.1,
                  lease_want: int = 2048) -> dict | None:
    """Per-decision-RPC cost, leases off vs on, on the SAME live server and
    the SAME Zipfian flow stream (same seed → serve_client replays one
    sequence). The ``rpc_reduction`` ratio is the wire-rev-5 headline: how
    many per-decision RPCs the lease protocol deleted. Leases-off runs
    first so the on-run cannot warm the off-run's flow rows. The stream
    targets 1024 of the server's flows: a single closed-loop client can
    keep ~1k leases warm against the production 500ms TTL (the gated
    controlled-TTL variant is benchmarks/lease_smoke.py); folding a
    Zipfian stream over all 100k rows would measure TTL churn, not the
    protocol."""
    lease_flows = min(n_flows, 1024)
    common = ("--port", port, "--mode", "lease", "--seconds", seconds,
              "--flows", lease_flows, "--seed", seed, "--zipf-alpha", alpha,
              "--lease-want", lease_want)
    off = _spawn_clients([common], timeout_s=seconds * 4 + 120)
    on = _spawn_clients([(*common, "--lease")], timeout_s=seconds * 4 + 120)
    if not off or not on:
        return None
    off, on = off[0], on[0]
    denom = max(on["rpcs_per_decision"], 1e-9)
    return {
        "zipf_alpha": alpha,
        "lease_want": lease_want,
        "off": off,
        "on": on,
        "rpcs_per_decision_off": off["rpcs_per_decision"],
        "rpcs_per_decision_on": on["rpcs_per_decision"],
        "rpc_reduction": round(off["rpcs_per_decision"] / denom, 1),
        "local_admit_rate": on["local_admit_rate"],
    }


def measure_ha(deadline_ms: float = 500.0,
               fallback_probes: int = 400) -> dict:
    """Lightweight in-process failover probe for the bench artifact: two
    small token servers, stop the primary mid-load, record how long the
    failover client takes to converge on the standby; then stop the standby
    and record the fallback window's blocked-rate with every request still
    resolving locally. In-process ``stop()`` stands in for the kill here —
    the honest SIGKILL variant is ``benchmarks/ha_drill.py`` (CI smoke)."""
    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
    from sentinel_tpu.engine.rules import ThresholdMode
    from sentinel_tpu.ha import (
        FailoverTokenClient,
        FallbackAction,
        FallbackRule,
        LocalFallbackPolicy,
    )

    flow = 42

    def _server():
        svc = DefaultTokenService(
            EngineConfig(max_flows=64, max_namespaces=4, batch_size=64)
        )
        svc.load_rules([ClusterFlowRule(flow, 1e9, ThresholdMode.GLOBAL)])
        server = TokenServer(svc, port=0)
        server.start()
        return server

    primary, standby = _server(), _server()
    policy = LocalFallbackPolicy(
        [FallbackRule(flow, FallbackAction.THROTTLE,
                      count=fallback_probes / 4)]
    )
    client = FailoverTokenClient(
        [("127.0.0.1", primary.port), ("127.0.0.1", standby.port)],
        timeout_ms=200, failure_threshold=1, deadline_ms=deadline_ms,
        fallback=policy,
    )
    converged_ms = None
    try:
        for _ in range(20):
            client.request_token(flow)
        primary.stop()
        t0 = time.perf_counter()
        standby_ep = f"127.0.0.1:{standby.port}"
        while time.perf_counter() - t0 < 10.0:
            r = client.request_token(flow)
            if r.ok and str(client.active_endpoint) == standby_ep:
                converged_ms = (time.perf_counter() - t0) * 1e3
                break
        standby.stop()
        for _ in range(fallback_probes):
            client.request_token(flow)  # resolves via the local fallback
    finally:
        client.close()
        primary.stop()
        standby.stop()
    return {
        "failover_convergence_ms": (
            round(converged_ms, 1) if converged_ms is not None else None
        ),
        "failover_deadline_ms": deadline_ms,
        "fallback_blocked_rate": policy.stats()["blocked_rate"],
        "fallback_requests": fallback_probes,
    }


def measure_hier(budget_qps: float = 200.0, decisions: int = 2000,
                 reconcile_iters: int = 200) -> dict:
    """Hierarchy-tier probe for the bench artifact: two in-process pods
    split one global budget through a co-located coordinator (pod A's
    ordinary front door carries the share traffic), then three numbers:

    - per-pod share after the control plane settles (the water-fill
      outcome the dashboard would show),
    - ``reconcile_once`` wall latency p50/p99 with live demand (the
      DCN-tier loop's cost — what bounds how low ``reconcile_ms`` can go,
      docs/PERF.md),
    - cross-pod RPCs per decision over a decision burst — gated at
      exactly 0: the whole point of the tier is that admission never
      leaves the pod."""
    from sentinel_tpu.cluster.hierarchy import (
        GlobalBudgetCoordinator,
        GlobalFlowBudget,
        PodShareAgent,
    )
    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
    from sentinel_tpu.engine.rules import ThresholdMode

    flow = 42
    cfg = EngineConfig(max_flows=64, max_namespaces=4, batch_size=64)
    window_s = cfg.bucket_ms * 10 / 1000.0
    svc_a = DefaultTokenService(cfg)
    svc_b = DefaultTokenService(cfg)
    for svc in (svc_a, svc_b):
        svc.load_rules(
            [ClusterFlowRule(flow, budget_qps, ThresholdMode.GLOBAL)]
        )
    coord = GlobalBudgetCoordinator(
        [GlobalFlowBudget(flow, budget_qps, window_s)]
    )
    svc_a.attach_hierarchy(coord)
    server = TokenServer(svc_a, port=0, metrics_port=0)
    server.start()
    ep = f"127.0.0.1:{server.port}"
    ag_a = PodShareAgent(svc_a, [ep], "pod-a", [flow])
    ag_b = PodShareAgent(svc_b, [ep], "pod-b", [flow])
    try:
        # settle the control plane: report → reconcile → renew, twice
        for _ in range(2):
            ag_a.tick()
            ag_b.tick()
            coord.reconcile_once()
        ag_a.tick()
        ag_b.tick()
        # skewed demand so the timed reconcile passes do real water-fill
        for _ in range(50):
            svc_a.request_token(flow)
        ag_a.tick()
        ag_b.tick()
        lat_ms = []
        for _ in range(reconcile_iters):
            t0 = time.perf_counter()
            coord.reconcile_once()
            lat_ms.append((time.perf_counter() - t0) * 1e3)
        lat = np.asarray(lat_ms)
        # the hot-path gate: decisions on both pods with the control plane
        # quiet must not move the agents' RPC counters at all
        rpc0 = ag_a.stats()["agent_rpcs"] + ag_b.stats()["agent_rpcs"]
        for _ in range(decisions // 2):
            svc_a.request_token(flow)
            svc_b.request_token(flow)
        rpc_delta = (
            ag_a.stats()["agent_rpcs"] + ag_b.stats()["agent_rpcs"] - rpc0
        )
        return {
            "budget_tokens": coord.budget_of(flow),
            "share_per_pod": {
                "pod-a": ag_a.shares().get(flow, 0),
                "pod-b": ag_b.shares().get(flow, 0),
            },
            "reconcile_p50_ms": round(float(np.percentile(lat, 50)), 4),
            "reconcile_p99_ms": round(float(np.percentile(lat, 99)), 4),
            "decisions": decisions,
            "cross_pod_rpcs_per_decision": round(
                rpc_delta / max(decisions, 1), 6
            ),
        }
    finally:
        ag_a.close()
        ag_b.close()
        coord.stop()
        server.stop()


def serve_measure(native: bool = True, closed_kw=None, sweep_rates=None,
                  n_flows: int = 100_000, max_batch: int = 16384,
                  n_dispatchers: int = None, budget_s: float = None,
                  intake_shards: int = 1,
                  single_door_baseline: bool = False,
                  mesh_devices: int = 0,
                  mesh_control: bool = True) -> dict:
    """Full measurement on the CURRENT backend (caller configured jax).

    ``closed_kw`` may be one closed-loop config (dict) or a list of
    candidate configs: each is measured and the highest served rate becomes
    the headline ``closed_loop`` (the rest land in ``closed_loop_alts``) —
    the best frame shape is backend-dependent (per-frame host work vs
    in-flight depth) and an 8-second probe per candidate is cheaper than
    guessing wrong. ``budget_s`` bounds the whole measurement so a caller
    can always exit cleanly inside its deadline."""
    import jax

    t0_all = time.perf_counter()
    deadline_ts = None if budget_s is None else t0_all + budget_s
    backend = jax.default_backend()
    if n_dispatchers is None:
        # more dispatcher threads = more device steps in flight (each
        # chains on the state future), the lever against per-dispatch
        # latency on the chip. On CPU extra dispatchers just time-slice
        # the host.
        n_dispatchers = 4 if backend == "tpu" else 2
    # bucket ladder per backend: on TPU big buckets amortize dispatch RTT;
    # on CPU the step is shape-proportional, so padding a light pull to
    # 16384 wastes host time — give it smaller rungs
    buckets = (4096, 16384) if backend == "tpu" else (1024, 4096, 16384)
    service, server, front_door = build_server(
        n_flows=n_flows, max_batch=max_batch, native=native,
        n_dispatchers=n_dispatchers, serve_buckets=buckets,
        intake_shards=intake_shards, mesh_devices=mesh_devices,
    )
    try:
        candidates = (closed_kw if isinstance(closed_kw, (list, tuple))
                      else [closed_kw or {}])
        winning_kw = candidates[0] or {}
        # server-side stage breakdown per candidate: the server runs
        # in-process, so its pipeline histograms (queue wait / decide /
        # write / batch size) are snapshotted per closed-loop round and
        # ride the artifact next to the client-observed RTTs
        from sentinel_tpu.metrics.server import server_metrics
        stage_metrics = server_metrics()
        closed, alts = None, []
        for kw in candidates:
            if closed is not None and deadline_ts is not None \
                    and time.perf_counter() > deadline_ts:
                break  # keep what we have; budget exhausted
            stage_metrics.reset()
            c = run_closed(server.port, n_flows=n_flows, **kw)
            c["stage_latency_ms"] = stage_metrics.stage_snapshot()
            # frame-fusion evidence + per-lane occupancy: what fraction of
            # the measurement window each lane spent busy (sum of its stage
            # times over wall time; reply occupancy averages over the
            # n_dispatchers reply threads). Occupancy ≈ 1.0 marks the
            # pipeline's bottleneck lane.
            wall_ms = max(c.get("wall_s") or 0.0, 1e-9) * 1e3
            stages = c["stage_latency_ms"]

            def _busy(*names, lanes=1):
                total = sum(
                    (stages.get(nm) or {}).get("sum") or 0.0
                    for nm in names
                )
                # clamp to [0, 1]: the door/stage counters are relaxed
                # atomics read without a consistent snapshot (see
                # Frontdoor.stats()), so a diff racing a live lane can
                # land a hair outside the window
                return round(min(max(total / (wall_ms * lanes), 0.0), 1.0), 4)

            c["fusion"] = {
                "fused_frames_total": stage_metrics.fused_frames_total,
                "fused_depth": stage_metrics.fused_depth.snapshot(),
                "lane_occupancy": {
                    "intake": _busy("intake_ms"),
                    "device": _busy("dispatch_ms"),
                    "reply": _busy(
                        "decide_ms", "write_ms", lanes=n_dispatchers
                    ),
                },
            }
            # zero-copy host path evidence: per-shard intake occupancy
            # (busy_ms over the measurement wall) and how many bytes the
            # host actually copied per served verdict — the number the
            # direct-to-staging decode + scatter encode are driving down
            shard_snap = stages.get("intake_shards") or {}
            c["host_path"] = {
                "intake_shards": (
                    intake_shards if front_door == "native-epoll" else None
                ),
                "shard_occupancy": {
                    k: round(min(max(
                        (v.get("busy_ms") or 0.0) / wall_ms, 0.0
                    ), 1.0), 4)
                    for k, v in sorted(shard_snap.items())
                },
                "shard_pulls": {
                    k: int(v.get("pulls") or 0)
                    for k, v in sorted(shard_snap.items())
                },
                "bytes_copied_per_verdict": round(
                    (stages.get("host_copy_bytes_total") or 0)
                    / max(c["verdicts_ok"], 1), 2,
                ),
            }
            if closed is None or c["verdicts_per_sec"] > \
                    closed["verdicts_per_sec"]:
                if closed is not None:
                    alts.append(closed)
                closed = c
                winning_kw = kw or {}
            else:
                alts.append(c)
        if sweep_rates is None:
            sweep_rates = (250_000, 500_000, 1_000_000, 1_500_000,
                           2_000_000, 3_000_000)
        curve = run_sweep(server.port, sweep_rates, n_flows=n_flows,
                          deadline_ts=deadline_ts)
        # lease amortization on the live server: per-decision RPCs with the
        # rev-5 protocol off vs on, same Zipfian stream. Never aborts the
        # measurement — a broken probe surfaces as lease=None.
        try:
            lease_block = measure_lease(server.port, n_flows=n_flows)
        except Exception as e:
            print(f"serve_bench: lease probe failed: {e!r}", file=sys.stderr)
            lease_block = None
        # same-host service ceiling (no TCP) for the front-door ratio
        rng = np.random.default_rng(0)
        ids = rng.integers(0, n_flows, size=max_batch).astype(np.int64)
        for _ in range(3):
            service.request_batch_arrays(ids)
        t0 = time.perf_counter()
        reps = 20
        for _ in range(reps):
            service.request_batch_arrays(ids)
        ceiling = max_batch * reps / (time.perf_counter() - t0)
    finally:
        server.stop()
        service.close()
    baseline = None
    if single_door_baseline and intake_shards > 1 \
            and front_door == "native-epoll":
        # same-run, same-client-config single-door control: the honest
        # denominator for any sharding-speedup claim (same host, same
        # backend warmth, same subprocess client build)
        svc_b, srv_b, _ = build_server(
            n_flows=n_flows, max_batch=max_batch, native=native,
            n_dispatchers=n_dispatchers, serve_buckets=buckets,
            intake_shards=1,
        )
        try:
            b = run_closed(srv_b.port, n_flows=n_flows, **winning_kw)
            baseline = {
                "intake_shards": 1,
                "verdicts_per_sec": b["verdicts_per_sec"],
                "p50_ms": b["p50_ms"],
                "p99_ms": b["p99_ms"],
                "errors": b["errors"],
            }
        finally:
            srv_b.stop()
            svc_b.close()
    mesh_block = None
    if mesh_devices:
        mesh_block = {
            "n_devices": mesh_devices,
            "per_shard_rows": n_flows // mesh_devices,
            "service_ceiling_vps": round(ceiling),
        }
        if mesh_control:
            # same-run single-shard control: same host, same client config,
            # same backend warmth — the honest denominator for any mesh
            # claim. The ceiling ratio isolates psum-stitch + shard_map
            # overhead per step (the TCP numbers fold in the host path,
            # which the mesh leaves untouched by design).
            svc_c, srv_c, _ = build_server(
                n_flows=n_flows, max_batch=max_batch, native=native,
                n_dispatchers=n_dispatchers, serve_buckets=buckets,
                intake_shards=intake_shards, mesh_devices=0,
            )
            try:
                c = run_closed(srv_c.port, n_flows=n_flows, **winning_kw)
                rng = np.random.default_rng(0)
                ids = rng.integers(0, n_flows, size=max_batch).astype(
                    np.int64
                )
                for _ in range(3):
                    svc_c.request_batch_arrays(ids)
                t0 = time.perf_counter()
                reps = 20
                for _ in range(reps):
                    svc_c.request_batch_arrays(ids)
                ceiling_c = max_batch * reps / (time.perf_counter() - t0)
                mesh_block["single_shard_control"] = {
                    "verdicts_per_sec": c["verdicts_per_sec"],
                    "p50_ms": c["p50_ms"],
                    "p99_ms": c["p99_ms"],
                    "errors": c["errors"],
                    "service_ceiling_vps": round(ceiling_c),
                }
                # >1 means the sharded step costs that factor more per
                # dispatch than the single-shard step on THIS backend (on
                # a 1-core CPU mesh all shards time-slice one core, so
                # expect well above 1; on real ICI this is the psum tax)
                mesh_block["psum_overhead_step_ratio"] = round(
                    ceiling_c / ceiling, 3
                ) if ceiling else None
            finally:
                srv_c.stop()
                svc_c.close()
    op = operating_point(curve)
    # HA probe rides the artifact: failover convergence + the all-down
    # fallback window's blocked-rate. Never aborts the measurement — a
    # broken probe surfaces as ha=None next to valid serve numbers.
    try:
        ha = measure_ha()
    except Exception as e:
        print(f"serve_bench: ha probe failed: {e!r}", file=sys.stderr)
        ha = None
    # hierarchy-tier probe: per-pod share split, reconcile latency, and
    # the zero-cross-pod-RPCs-per-decision gate. Same contract as the ha
    # probe: a broken probe surfaces as hier=None, never as a lost run.
    try:
        hier = measure_hier()
    except Exception as e:
        print(f"serve_bench: hier probe failed: {e!r}", file=sys.stderr)
        hier = None
    return {
        "backend": backend,
        # only the native door has dispatcher threads; the asyncio fallback
        # ignores the knob, and reporting it there would let readers
        # attribute throughput to a dispatcher count never in effect
        "n_dispatchers": (
            n_dispatchers if front_door == "native-epoll" else None
        ),
        # configured device-lane fusion budget (pulls per dispatch); the
        # per-candidate closed_loop.fusion block records the depths the
        # token service's ladder ACTUALLY fused under that load
        "fusion_depth": getattr(server, "fuse_depth", None),
        "intake_shards": (
            intake_shards if front_door == "native-epoll" else None
        ),
        "front_door": front_door,
        "verdicts_per_sec": closed["verdicts_per_sec"],
        "p50_ms": closed["p50_ms"],
        "p99_ms": closed["p99_ms"],
        "closed_loop": closed,
        **({"closed_loop_alts": alts} if alts else {}),
        "load_latency_curve": curve,
        "operating_point": op,
        "slo_p99_ms": SLO_P99_MS,
        "service_ceiling_vps": round(ceiling),
        "served_over_ceiling": round(
            closed["verdicts_per_sec"] / ceiling, 3
        ) if ceiling else None,
        "ha": ha,
        "hier": hier,
        "lease": lease_block,
        **({"mesh": mesh_block} if mesh_block else {}),
        **({"single_door_baseline": baseline,
            "sharding_speedup": round(
                closed["verdicts_per_sec"]
                / max(baseline["verdicts_per_sec"], 1), 3,
            )} if baseline else {}),
        "host_cores": os.cpu_count(),
    }


def _cpu_self_s() -> float:
    """This process's consumed CPU seconds (user+sys, all threads) — the
    in-process server/door side of the host-cost ledger."""
    import resource

    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime


def _us_pcts(us: np.ndarray) -> dict:
    return {
        "p50_us": round(float(np.percentile(us, 50)), 2),
        "p90_us": round(float(np.percentile(us, 90)), 2),
        "p99_us": round(float(np.percentile(us, 99)), 2),
        "max_us": round(float(us.max()), 2),
    } if us.size else {}


def shm_echo_rtt(batch: int = 1, iters: int = 20_000) -> dict:
    """Raw ring transport round trip: C echo loop behind the door, C
    send+spin-recv loop in the client, both in THIS process — no Python,
    no codecs, no device inside the timed region. The per-iteration RTTs
    are the co-located door's latency claim; the CPU delta over the run is
    the shm transport's host-cost floor (both sides included)."""
    import shutil
    import tempfile

    from sentinel_tpu.cluster import protocol as P
    from sentinel_tpu.native.lib import ShmDoor, ShmRingClient

    d = tempfile.mkdtemp(prefix="sentinel-shm-rtt-")
    door = ShmDoor(d)
    door.echo_start()
    ids = (np.arange(batch, dtype=np.int64) % 1024)
    frame = P.encode_batch_request(1, ids)
    ring = ShmRingClient(d, n_slots=16)
    try:
        ring.rtt_probe(frame, iters=min(2000, iters))  # warmup
        s0 = door.stats()
        cpu0, t0 = _cpu_self_s(), time.perf_counter()
        ns = ring.rtt_probe(frame, iters=iters)
        wall = time.perf_counter() - t0
        cpu = _cpu_self_s() - cpu0
        s1 = door.stats()
    finally:
        ring.close()
        door.echo_stop()
        door.stop()
        shutil.rmtree(d, ignore_errors=True)
    us = np.asarray(ns, np.float64) / 1e3
    frames = max(int(us.size), 1)
    # doorbell amortization evidence: futex rings per frame on the server
    # side (counter deltas clamp at zero — relaxed atomics, see stats())
    doorbells = max(s1["shm_doorbells"] - s0["shm_doorbells"], 0)
    return {
        "rows_per_frame": batch,
        "iters": int(us.size),
        "rtt": _us_pcts(us),
        "cpu_us_per_frame": round(cpu / frames * 1e6, 3),
        "cpu_us_per_verdict": round(cpu / (frames * batch) * 1e6, 4),
        "server_doorbells_per_frame": round(doorbells / frames, 4),
        "wall_s": round(wall, 3),
    }


def door_echo_cost(kind: str, batch: int, frames_per_sec: float,
                   seconds: float = 4.0, window: int = 128) -> dict:
    """Per-verdict host cost of ONE front door behind its pure-C echo loop
    (``sn_fd_echo_start`` / ``sn_shm_echo_start`` — the identical wait→
    all-GRANTED-submit loop, compiled) — no token service, no device step,
    no Python on the serving side. What differs between a tcp and an shm
    run is exactly the transport: epoll + recv/send syscalls + kernel
    copies + client socket framing (tcp) vs. ring memcpys and an
    occasionally-rung futex doorbell (shm); the wire decode/encode is the
    same C codec in both doors, and the client is the same
    ``serve_client.py`` open-loop driver.

    ``frames_per_sec`` picks the regime: offer beyond the door's capacity
    and the in-flight window cap turns the run into a closed loop
    ``window`` deep (saturation — doorbells amortize over slot bursts);
    offer a trickle and every frame travels alone (paced — each one pays
    the full wake/sleep round). ``server_cpu`` is this process's rusage
    delta (the door side); the client reports its own CPU."""
    import shutil
    import tempfile

    from sentinel_tpu.native.lib import Frontdoor, ShmDoor

    d = None
    if kind == "shm":
        d = tempfile.mkdtemp(prefix="sentinel-shm-cost-")
        door = ShmDoor(d)
        port = 0
    else:
        door = Frontdoor("127.0.0.1", 0)
        port = door.port
    door.echo_start()
    transport = ("--transport", "shm", "--shm-dir", d) if d else ()
    try:
        cpu0 = _cpu_self_s()
        docs = _spawn_clients(
            [
                ("--port", port, "--mode", "open", "--batch", batch,
                 "--rate", frames_per_sec * batch, "--seconds", seconds,
                 "--flows", 1024, "--window", window, "--seed", 0,
                 *transport)
            ],
            timeout_s=seconds * 4 + 120,
        )
        server_cpu = _cpu_self_s() - cpu0
        stats = door.stats()
    finally:
        door.echo_stop()
        door.stop()
        if d:
            shutil.rmtree(d, ignore_errors=True)
    frames = sum(doc["frames_sent"] for doc in docs)
    verdicts = sum(doc["verdicts_ok"] for doc in docs)
    client_cpu = sum(doc.get("cpu_s") or 0.0 for doc in docs)
    out = {
        "transport": kind,
        "rows_per_frame": batch,
        "offered_frames_per_sec": round(frames_per_sec),
        "frames": frames,
        "achieved_frames_per_sec": round(frames / max(seconds, 1e-9)),
        "verdicts": verdicts,
        "frames_dropped": sum(doc["frames_dropped"] for doc in docs),
        "frames_lost": sum(doc["frames_lost"] for doc in docs),
        "server_cpu_s": round(server_cpu, 4),
        "client_cpu_s": round(client_cpu, 4),
        "server_cpu_us_per_frame": round(
            server_cpu / max(frames, 1) * 1e6, 4
        ),
        "server_cpu_us_per_verdict": round(
            server_cpu / max(verdicts, 1) * 1e6, 4
        ),
        "total_host_cpu_us_per_verdict": round(
            (server_cpu + client_cpu) / max(verdicts, 1) * 1e6, 4
        ),
    }
    if kind == "shm":
        # syscall-amortization evidence: futexes actually rung per frame
        out["doorbells_per_frame"] = round(
            stats["shm_doorbells"] / max(stats["frames_in"], 1), 4
        )
        out["polls_per_frame"] = round(
            stats["shm_polls"] / max(stats["frames_in"], 1), 4
        )
    return out


def intake_matrix(shards=(1, 2, 4), seconds: float = 3.0,
                  n_flows: int = 10_000) -> list:
    """Closed-loop served rate for every intake-shard count × transport
    cell, each against a fresh full server (same process, so kernel
    compiles are warm after the first cell). On hosts with fewer cores
    than shards the cells share one core — the artifact records
    ``host_cores`` so a flat column reads as the core ceiling it is, not
    as a sharding defect."""
    import shutil
    import tempfile

    cells = []
    for s in shards:
        for transport in ("tcp", "shm"):
            d = tempfile.mkdtemp(prefix="sentinel-shm-mx-") \
                if transport == "shm" else None
            service, server, front_door = build_server(
                n_flows=n_flows, max_batch=4096, serve_buckets=(1024, 4096),
                native=True, n_dispatchers=2, fuse_depth=4,
                intake_shards=s, shm_dir=d,
            )
            try:
                c = run_closed(
                    server.port, clients=2, batch=4096, pipeline=4,
                    seconds=seconds, n_flows=n_flows, shm_dir=d,
                )
            finally:
                server.stop()
                service.close()
                if d:
                    shutil.rmtree(d, ignore_errors=True)
            cells.append({
                "intake_shards": s,
                "transport": transport,
                "front_door": front_door,
                "verdicts_per_sec": c["verdicts_per_sec"],
                "p50_ms": c["p50_ms"],
                "p99_ms": c["p99_ms"],
                "errors": c["errors"],
            })
    return cells


def shm_measure(seconds: float = 6.0, sidecar_batch: int = 16,
                bulk_batch: int = 4096, matrix_shards=(1, 2, 4)) -> dict:
    """The co-located-door artifact: ring RTT distribution, per-verdict
    host cost vs a SAME-RUN TCP control, and the intake-shard matrix.

    Host cost compares the two doors behind the identical pure-C echo loop
    in two regimes per frame shape: **saturated** (offered load far past
    the door, so the client's in-flight window turns the run into a deep
    closed loop — the doorbell futex amortizes over slot bursts and the
    shm door approaches its zero-syscall steady state) and **paced** (a
    trickle, every frame travels alone and pays the full wake/sleep
    round). The headline ``door_cost_ratio`` is the server-side CPU per
    verdict, tcp/shm, at saturation: the door is what this PR replaced,
    the client-side codec work is the same protocol.py code over either
    transport by construction, and saturation is where a co-located
    sidecar fleet actually operates when it matters."""
    rtt_1 = shm_echo_rtt(batch=1)
    rtt_sidecar = shm_echo_rtt(batch=sidecar_batch)
    # offered frames/s per (shape, regime): saturated offers well past the
    # measured 1-core echo ceiling (~30-60k f/s small frames, ~5-15k bulk);
    # paced sits far below it
    offers = {
        "sidecar": {"saturated": 150_000, "paced": 4_000},
        "bulk": {"saturated": 25_000, "paced": 800},
    }
    cost = {}
    for b_name, b in (("sidecar", sidecar_batch), ("bulk", bulk_batch)):
        block = {"rows_per_frame": b}
        for regime, fps in offers[b_name].items():
            window = 128 if regime == "saturated" else 8
            tcp = door_echo_cost("tcp", batch=b, frames_per_sec=fps,
                                 seconds=seconds, window=window)
            shm = door_echo_cost("shm", batch=b, frames_per_sec=fps,
                                 seconds=seconds, window=window)
            a, bb = (tcp["server_cpu_us_per_verdict"],
                     shm["server_cpu_us_per_verdict"])
            at, bt = (tcp["total_host_cpu_us_per_verdict"],
                      shm["total_host_cpu_us_per_verdict"])
            block[regime] = {
                "tcp": tcp,
                "shm": shm,
                "door_cost_ratio": round(a / bb, 2) if bb else None,
                "total_host_cpu_ratio": round(at / bt, 2) if bt else None,
            }
        cost[b_name] = block
    matrix = intake_matrix(shards=matrix_shards)

    def _vps(s, tr):
        return next(
            (c["verdicts_per_sec"] for c in matrix
             if c["intake_shards"] == s and c["transport"] == tr), None,
        )

    scaling = {
        tr: round(_vps(max(matrix_shards), tr) / _vps(1, tr), 3)
        for tr in ("tcp", "shm")
        if _vps(1, tr) and _vps(max(matrix_shards), tr)
    }
    return {
        "ring_rtt_1row": rtt_1,
        "ring_rtt_sidecar": rtt_sidecar,
        "host_cost": cost,
        "intake_matrix": matrix,
        "intake_scaling_at_max_shards": scaling,
        "host_cores": os.cpu_count(),
    }


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--no-native", action="store_true")
    ap.add_argument("--flows", type=int, default=100_000)
    ap.add_argument("--intake-shards", type=int, default=1,
                    help="SO_REUSEPORT intake shards on the native door")
    ap.add_argument("--single-door-baseline", action="store_true",
                    help="with --intake-shards > 1, also measure a "
                         "same-config intake_shards=1 control run")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="back the service with a flow-sharded mesh over N "
                         "devices; off a real pod this forces N virtual CPU "
                         "devices. Records a `mesh` artifact block with a "
                         "same-run single-shard control")
    ap.add_argument("--no-mesh-control", action="store_true",
                    help="skip the single-shard control run in mesh mode")
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--pipeline", type=int, default=None)
    ap.add_argument("--shm", action="store_true",
                    help="measure the co-located shared-memory ring door: "
                         "ring RTT distribution, per-verdict host cost vs "
                         "a same-run TCP control, and the intake-shard × "
                         "transport matrix. Writes shm-door-<ts>.json")
    args = ap.parse_args()
    if args.shm:
        import jax

        jax.config.update("jax_platforms", "cpu")
        from sentinel_tpu.native.lib import shm_available

        if not shm_available():
            print("shm door not built; nothing to measure", file=sys.stderr)
            sys.exit(2)
        doc = shm_measure()
        line = json.dumps(doc, indent=2)
        print(line)
        d = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "results"
        )
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(
                d, f"shm-door-{time.strftime('%Y%m%d-%H%M%S')}.json"),
                "w") as f:
            f.write(line + "\n")
        return
    closed_kw = {
        k: v for k, v in (
            ("clients", args.clients), ("batch", args.batch),
            ("pipeline", args.pipeline),
        ) if v is not None
    } or None
    import jax

    # NOTE: checked via env, not jax.default_backend() — that call would
    # initialize the backend before force_virtual_cpu_devices can act
    on_tpu = os.environ.get("JAX_PLATFORMS", "").startswith("tpu")
    if args.mesh_devices and not on_tpu:
        # virtual CPU mesh: must be forced before backend creation
        force_virtual_cpu_devices(args.mesh_devices)
    elif args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from sentinel_tpu.core.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    doc = serve_measure(
        native=not args.no_native, n_flows=args.flows,
        closed_kw=closed_kw, intake_shards=args.intake_shards,
        single_door_baseline=args.single_door_baseline,
        mesh_devices=args.mesh_devices,
        mesh_control=not args.no_mesh_control,
    )
    line = json.dumps(
        {
            "metric": "served_end_to_end",
            "value": doc["verdicts_per_sec"],
            "unit": "verdicts/s",
            "vs_baseline": round(doc["verdicts_per_sec"] / 30_000, 2),
            "extra": doc,
        }
    )
    print(line)
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(
            d, f"serve-{time.strftime('%Y%m%d-%H%M%S')}.json"), "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
