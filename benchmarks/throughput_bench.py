"""End-to-end token-server throughput benchmark (the served rate).

Round-2 review: the headline bench was a device-kernel scan; the demonstrated
*served* rate was 4,783 rps — three orders below the kernel. This harness
measures verdicts/second through the FULL serving path: client processes →
BATCH_FLOW frames over TCP → asyncio front door(s) → micro-batcher → device
decision step → vectorized response frames → client decode.

Clients are separate OS processes (no shared GIL with the server); each runs
``pipeline`` threads that keep batch frames in flight back-to-back, modeling
a fleet of sidecar clients that batch like the reference's netty clients
pipeline channel writes.

Usage: ``python benchmarks/throughput_bench.py [--clients 8] [--batch 512]
[--pipeline 2] [--seconds 5] [--loops 2]``
Prints ONE JSON line and appends a copy under ``benchmarks/results/``.
"""

from __future__ import annotations

import os as _os
import sys as _sys

_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO not in _sys.path:
    _sys.path.insert(0, _REPO)

import argparse
import json
import multiprocessing as mp
import os
import time


def _client_worker(k: int, port: int, batch: int, pipeline: int,
                   seconds: float, n_flows: int, out_q) -> None:
    # child process: only sockets + numpy. One process per chip: pinned to
    # the CPU before anything can import jax
    os.environ["JAX_PLATFORMS"] = "cpu"
    import threading

    import numpy as np

    from sentinel_tpu.cluster.client import TokenClient

    client = TokenClient("127.0.0.1", port, timeout_ms=5000)
    rng = np.random.default_rng(k)
    done = []
    errors = []
    stop_at = time.perf_counter() + seconds

    def pump(t: int) -> None:
        flow_ids = rng.integers(0, n_flows, size=batch).astype(np.int64)
        n_ok = 0
        n_err = 0
        while time.perf_counter() < stop_at:
            out = client.request_batch_arrays(flow_ids)
            if out is None:
                n_err += batch
            else:
                n_ok += batch
        done.append(n_ok)
        errors.append(n_err)

    # warmup (connection + compiled-shape route)
    client.request_batch_arrays(np.zeros(batch, np.int64))
    threads = [
        threading.Thread(target=pump, args=(t,)) for t in range(pipeline)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    client.close()
    out_q.put((k, sum(done), sum(errors)))


def run(n_clients: int = 8, batch: int = 1024, pipeline: int = 3,
        seconds: float = 5.0, n_flows: int = 1024, n_loops: int = 2,
        max_batch: int = 4096, port: int = 0, native: bool = False) -> dict:
    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
    from sentinel_tpu.engine.rules import ThresholdMode

    config = EngineConfig(max_flows=n_flows, max_namespaces=8, batch_size=max_batch)
    service = DefaultTokenService(config)
    service.load_rules(
        [
            ClusterFlowRule(flow_id=i, count=1e9, mode=ThresholdMode.GLOBAL,
                            namespace=f"ns{i % 8}")
            for i in range(n_flows)
        ],
        ns_max_qps=1e12,
    )
    if native:
        # asked for by name: not loadable is an error, not another door
        from sentinel_tpu.cluster.server_native import NativeTokenServer
    if native:
        server = NativeTokenServer(service, host="127.0.0.1", port=port,
                                   max_batch=max_batch)
    else:
        server = TokenServer(service, host="127.0.0.1", port=port,
                             max_batch=max_batch, n_loops=n_loops)
    server.start()
    port = server.port

    # stage histograms cover exactly the measurement window (warmup/compile
    # excluded) so the artifact's p50/p99 are steady-state
    from sentinel_tpu.metrics.server import server_metrics
    server_metrics().reset()

    # spawn, not fork: the parent has initialised the backend by now (it
    # holds the chip, and has threads), which a forked child would inherit
    ctx = mp.get_context("spawn")
    out_q = ctx.Queue()
    procs = [
        ctx.Process(target=_client_worker,
                    args=(k, port, batch, pipeline, seconds, n_flows, out_q),
                    daemon=True)
        for k in range(n_clients)
    ]
    t0 = time.perf_counter()
    for p in procs:
        p.start()
    results = [out_q.get(timeout=seconds * 4 + 60) for _ in procs]
    for p in procs:
        p.join(timeout=30)
    wall = time.perf_counter() - t0
    stage_latency = server_metrics().stage_snapshot()
    server.stop()
    service.close()

    total = sum(n for _, n, _ in results)
    errors = sum(e for _, _, e in results)
    rps = total / wall

    # same-host service ceiling (no TCP): what request_batch_arrays alone
    # sustains on this machine. served/ceiling is the front-door efficiency
    # — the VERDICT r3 metric ("served >= 1/3 of ceiling"); on a 1-core
    # host the clients share the core, so the ratio is conservative.
    # Reuses the already-warm service (server.stop() only parks the expiry
    # sweeper; the compiled steps and rule table stay live).
    import numpy as np

    rng = np.random.default_rng(0)
    ids = rng.integers(0, n_flows, size=max_batch).astype(np.int64)
    for _ in range(3):
        service.request_batch_arrays(ids)
    t0 = time.perf_counter()
    reps = 20
    for _ in range(reps):
        service.request_batch_arrays(ids)
    ceiling = max_batch * reps / (time.perf_counter() - t0)

    return {
        "metric": "e2e_token_server_throughput",
        "value": round(rps),
        "unit": "verdicts/s",
        "vs_baseline": round(rps / 30_000, 2),  # ref self-protection cap
        "extra": {
            "clients": n_clients,
            "batch_per_frame": batch,
            "pipeline_per_client": pipeline,
            "front_door": "native-epoll" if native else "asyncio",
            "server_loops": n_loops,
            "server_max_batch": max_batch,
            "seconds": seconds,
            "verdicts": total,
            "error_or_timeout": errors,
            "wall_s": round(wall, 2),
            "service_ceiling_vps": round(ceiling),
            "served_over_ceiling": round(rps / ceiling, 3),
            "host_cores": os.cpu_count(),
            "stage_latency_ms": stage_latency,
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--pipeline", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--flows", type=int, default=1024)
    ap.add_argument("--loops", type=int, default=2)
    ap.add_argument("--max-batch", type=int, default=4096)
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (8-process CPU harness)")
    ap.add_argument("--native", action="store_true",
                    help="serve through the native epoll front door")
    args = ap.parse_args()
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    result = run(args.clients, args.batch, args.pipeline, args.seconds,
                 args.flows, args.loops, args.max_batch, native=args.native)
    result["extra"]["backend"] = jax.default_backend()
    line = json.dumps(result)
    print(line)
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"throughput-{time.strftime('%Y%m%d-%H%M%S')}.json"),
              "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
