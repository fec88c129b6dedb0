"""Outcome feedback: entry → complete(rt, exception) → the metric surface
moving, with zero extra RPCs.

The cluster grants tokens; this demo closes the loop with what the
admitted work actually *did*. A client records each entry's completion
locally (``record_outcome(flow_id, rt_ms, exception=)``), and the
buffered rows ride the NEXT request frame as piggy-backed wire-rev-6
``OUTCOME_REPORT`` frames — fire-and-forget, no response, no extra round
trip. The server scatters them into per-flow device state columns
(windowed rt_sum / complete / exception counts plus a log2 RT histogram
for a device-side p99), and the whole metric surface moves:
``sentinel_flow_rt_avg_ms`` climbs as the simulated dependency slows,
``sentinel_flow_exception_qps`` lights up under an error burst, and the
drop counter accounts for a deliberately bogus report. See
docs/OBSERVABILITY.md "Outcome-feedback series".
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.server import TokenServer
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig, TokenStatus
from sentinel_tpu.metrics.server import server_metrics

FLOW = 707


def flow_gauge(name: str) -> float:
    """Read one per-flow gauge for FLOW off the live Prometheus body."""
    needle = f'{name}{{flow_id="{FLOW}"}} '
    for line in server_metrics().render().splitlines():
        if line.startswith(needle):
            return float(line.split()[-1])
    return 0.0


def main() -> None:
    svc = DefaultTokenService(EngineConfig(max_flows=16, max_namespaces=4))
    svc.load_rules([ClusterFlowRule(FLOW, 1000.0, namespace="checkout")])
    server = TokenServer(svc, port=0)
    server.start()
    # generous timeout: the first device step compiles, and a timed-out
    # request would silently skip that iteration's completion record
    client = TokenClient("127.0.0.1", server.port, timeout_ms=2000)
    print(f"token server on :{server.port} — flow {FLOW} (ns 'checkout')")

    try:
        # phase 1: healthy dependency, ~5ms completions
        for _ in range(20):
            if client.request_token(FLOW).status == TokenStatus.OK:
                client.record_outcome(FLOW, 5.0)
        client.request_token(FLOW)  # outcomes piggyback on this frame
        time.sleep(0.3)             # fire-and-forget: let the server land it
        healthy = flow_gauge("sentinel_flow_rt_avg_ms")
        print(f"healthy:  sentinel_flow_rt_avg_ms = {healthy:.1f}")

        # phase 2: the dependency slows 10x and starts throwing
        for i in range(20):
            if client.request_token(FLOW).status == TokenStatus.OK:
                client.record_outcome(FLOW, 50.0 + i, exception=(i % 4 == 0))
        client.record_outcome(FLOW, -12.0)  # bogus report: validated away
        client.request_token(FLOW)
        time.sleep(0.3)
        slow = flow_gauge("sentinel_flow_rt_avg_ms")
        exc = flow_gauge("sentinel_flow_exception_qps")
        p99 = flow_gauge("sentinel_flow_rt_p99_ms")
        print(f"degraded: sentinel_flow_rt_avg_ms = {slow:.1f} "
              f"(p99 {p99:.0f}ms), sentinel_flow_exception_qps = {exc:g}")

        stats = svc.outcome_stats()
        print(f"server accepted {stats['reported']} outcomes "
              f"({stats['exceptions']} exceptions), dropped "
              f"{dict(stats['dropped'])}")
        print(f"client piggybacked {client.outcome_stats()['frames']} "
              f"outcome frames onto request sends — extra RPCs: 0")
        if slow > healthy and exc > 0:
            print("the RT average moved with the dependency: "
                  "outcome loop closed")
    finally:
        client.close()
        server.stop()
        svc.close()


if __name__ == "__main__":
    main()
