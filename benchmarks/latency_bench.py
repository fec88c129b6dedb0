"""End-to-end token-verdict latency benchmark (the honest p99).

Measures the FULL serving path under concurrent load: client TCP socket →
asyncio front door → micro-batcher → device decision step → response →
client wakeup. This is the path the reference budgets 20ms for
(``ClusterConstants.java:44``); BASELINE.md's target is p99 < 2ms.

Round-1 review called out that ``bench.py``'s "p99" was ``min(lat)/chain`` —
a best-case mean. This harness records one wall-clock sample per request and
reports true percentiles. Clients run as separate OS processes (like real
clients) so their work doesn't share the server's GIL.

Usage: ``python benchmarks/latency_bench.py [--clients 8] [--requests 2000]``
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


def _client_worker(k: int, port: int, n_requests: int, n_flows: int,
                   timeout_ms: int, out_q) -> None:
    # child process: only sockets + numpy. One process per chip: pinned to
    # the CPU before anything can import jax
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    from sentinel_tpu.cluster.client import TokenClient
    from sentinel_tpu.engine import TokenStatus

    rng = np.random.default_rng(k)
    flow_ids = rng.integers(0, n_flows, size=n_requests)
    client = TokenClient("127.0.0.1", port, timeout_ms=timeout_ms)
    for _ in range(20):  # connection + route warmup, not timed
        client.request_token(int(flow_ids[0]))
    lat = np.empty(n_requests)
    err = 0
    for i in range(n_requests):
        t0 = time.perf_counter()
        res = client.request_token(int(flow_ids[i]))
        lat[i] = time.perf_counter() - t0
        if res.status not in (TokenStatus.OK, TokenStatus.SHOULD_WAIT,
                              TokenStatus.BLOCKED):
            err += 1
    client.close()
    out_q.put((k, lat, err))


def run(n_clients: int = 8, n_requests: int = 2000, n_flows: int = 1024,
        timeout_ms: int = 200, port: int = 0, n_loops: int = 2,
        native: bool = False) -> dict:
    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
    from sentinel_tpu.engine.rules import ThresholdMode

    config = EngineConfig(max_flows=n_flows, max_namespaces=8, batch_size=1024)
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
    # port 0 = ephemeral; read the bound port back after start
    if native:
        server = NativeTokenServer(service, host="127.0.0.1", port=port)
    else:
        server = TokenServer(service, host="127.0.0.1", port=port,
                             n_loops=n_loops)
    server.start()
    port = server.port

    # spawn, not fork: the parent has initialised the backend by now (it
    # holds the chip, and has threads), which a forked child would inherit
    ctx = mp.get_context("spawn")
    out_q = ctx.Queue()
    procs = [
        ctx.Process(target=_client_worker,
                    args=(k, port, n_requests, n_flows, timeout_ms, out_q),
                    daemon=True)
        for k in range(n_clients)
    ]
    t0 = time.perf_counter()
    for p in procs:
        p.start()
    results = [out_q.get(timeout=300) for _ in procs]
    for p in procs:
        p.join(timeout=30)
    wall = time.perf_counter() - t0
    server.stop()
    service.close()

    import numpy as np

    lat_ms = np.sort(np.concatenate([lat for _, lat, _ in results])) * 1e3
    total = len(lat_ms)
    errors = sum(e for _, _, e in results)

    def pct(p):
        return float(lat_ms[min(total - 1, int(p / 100 * total))])

    return {
        "metric": "e2e_token_verdict_latency",
        "value": round(pct(99), 3),
        "unit": "ms_p99",
        "vs_baseline": round(20.0 / max(pct(99), 1e-9), 2),  # 20ms ref budget
        "extra": {
            "p50_ms": round(pct(50), 3),
            "p90_ms": round(pct(90), 3),
            "p99_ms": round(pct(99), 3),
            "p999_ms": round(pct(99.9), 3),
            "max_ms": round(float(lat_ms[-1]), 3),
            "throughput_rps": round(total / wall),
            "clients": n_clients,
            "requests": total,
            "error_or_timeout": int(errors),
            "target_p99_ms": 2.0,
            "front_door": "native-epoll" if native else "asyncio",
            # loop/dispatcher knob of whichever front door actually ran
            "server_workers": (server.n_dispatchers if native else n_loops),
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--flows", type=int, default=1024)
    ap.add_argument("--native", action="store_true",
                    help="serve through the native epoll front door")
    args = ap.parse_args()
    result = run(args.clients, args.requests, args.flows, native=args.native)
    line = json.dumps(result)
    print(line)
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"latency-{time.strftime('%Y%m%d-%H%M%S')}.json"),
              "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
