"""Cluster flow control: a TPU-backed token server enforcing one global
budget across several TCP clients.

reference: ``sentinel-demo-cluster`` (embedded mode) — the server here is
``DefaultTokenService`` (micro-batched device kernel) behind the asyncio
transport; clients speak the 5-type binary protocol.
"""

import os
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.server import TokenServer
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
from sentinel_tpu.engine.rules import ThresholdMode


def main() -> None:
    svc = DefaultTokenService(EngineConfig(max_flows=64, max_namespaces=4,
                                           batch_size=128))
    svc.load_rules([
        ClusterFlowRule(flow_id=101, count=30.0, mode=ThresholdMode.GLOBAL)
    ])
    server = TokenServer(svc, port=0, metrics_port=0)
    server.start()
    print(f"token server on :{server.port} — flow 101 global budget 30/s "
          f"(metrics on :{server.metrics_port})")
    clients = [
        TokenClient("127.0.0.1", server.port, timeout_ms=2000) for _ in range(3)
    ]
    try:
        t0 = time.time()
        granted = [0, 0, 0]
        asked = 90  # round-robin across the clients, well over budget
        for i in range(asked):
            c = clients[i % 3]
            if c.request_token(101).ok:
                granted[i % 3] += 1
        elapsed = time.time() - t0
        windows = int(elapsed) + 1  # 1s sliding windows touched
        print(f"{asked} asks round-robin in {elapsed:.2f}s; granted per "
              f"client: {granted}")
        print(f"total granted {sum(granted)} ≤ {30 * windows} "
              f"(30/s GLOBAL budget × {windows} window(s)) — the three "
              f"clients share ONE budget")
        # the embedded Prometheus surface saw every verdict go by — see
        # docs/OBSERVABILITY.md for the full series reference
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.metrics_port}/metrics", timeout=5
        ) as rsp:
            scrape = rsp.read().decode()
        print("pipeline metrics scrape says:")
        for line in scrape.splitlines():
            name = line.split("{")[0].split(" ")[0]
            if name == "sentinel_server_verdicts_total" or (
                name.startswith("sentinel_server_") and name.endswith("_count")
            ):
                print(" ", line)
    finally:
        for c in clients:
            c.close()
        server.stop()


if __name__ == "__main__":
    main()
