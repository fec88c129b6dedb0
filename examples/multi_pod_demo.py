"""Two token-server pods in separate processes, one routing client.

reference: the multi-server deployment of ``sentinel-cluster`` — each
namespace's flows are owned by one token server and clients are pointed at
their server via assignment config. Here the DCN-tier pieces run live:
two OS processes each serve one namespace over real TCP, and a
``RoutingTokenClient`` routes ``flow_id → namespace → pod`` so the caller
never thinks about the partitioning (``cluster/routing.py``,
``cluster/namespaces.py``).

Each flow has a 3-QPS budget; six requests through the routing client show
exactly 3 admitted by the owning pod, and pods never see the other
namespace's flows.
"""

import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


FLOWS = {"ns-payments": (1, 2), "ns-search": (11, 12)}


def pod_main(namespace: str, port_file: str) -> None:
    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.core import clock as clock_mod
    from sentinel_tpu.core.clock import ManualClock
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
    from sentinel_tpu.engine.rules import ThresholdMode

    # frozen per-pod clock: the 3-of-6 admission assertion must not depend
    # on a wall-clock window roll mid-demo (FAST_EXAMPLES determinism)
    clock_mod.set_clock(ManualClock())
    service = DefaultTokenService(
        EngineConfig(max_flows=64, max_namespaces=4, batch_size=64),
        serve_buckets=(64,),
    )
    service.load_rules([
        ClusterFlowRule(flow_id=f, count=3.0, mode=ThresholdMode.GLOBAL,
                        namespace=namespace)
        for f in FLOWS[namespace]
    ])
    server = TokenServer(service, port=0)
    server.start()
    # atomic publication: the parent must never parse a half-written port
    tmp_path = port_file + ".tmp"
    with open(tmp_path, "w") as f:
        f.write(str(server.port))
    os.rename(tmp_path, port_file)
    # exit when the parent does: stdin is a pipe from the parent, so EOF
    # means it died (no orphan pods holding ports on a killed harness)
    sys.stdin.read()


def main() -> None:
    from sentinel_tpu.cluster.routing import RoutingTokenClient
    from sentinel_tpu.engine import TokenStatus

    tmp = tempfile.mkdtemp()
    pods = {}
    try:
        for ns in FLOWS:
            port_file = os.path.join(tmp, f"{ns}.port")
            # one process per chip: two pods cannot share one, so this demo
            # (routing across pods, not device speed) runs its pods on the CPU
            proc = subprocess.Popen(
                [sys.executable, __file__, "--pod", ns, port_file],
                stdin=subprocess.PIPE,
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
            )
            pods[ns] = [proc, port_file, None]
        for ns, entry in pods.items():
            deadline = time.time() + 60
            while time.time() < deadline:
                rc = entry[0].poll()
                assert rc is None, f"pod {ns} died at startup (rc={rc})"
                try:
                    with open(entry[1]) as f:
                        entry[2] = int(f.read())
                    break
                except (OSError, ValueError):
                    time.sleep(0.1)
            assert entry[2], f"pod {ns} never published its port"

        router = RoutingTokenClient(
            timeout_ms=5000,
            namespace_of={f: ns for ns, fs in FLOWS.items() for f in fs},
            pod_of={"ns-payments": "podA", "ns-search": "podB"},
            endpoints={"podA": ("127.0.0.1", pods["ns-payments"][2]),
                       "podB": ("127.0.0.1", pods["ns-search"][2])},
        )
        for ns, fs in FLOWS.items():
            flow = fs[0]
            results = router.request_batch([(flow, 1, False)] * 6)
            ok = sum(r.status == TokenStatus.OK for r in results)
            blocked = sum(r.status == TokenStatus.BLOCKED for r in results)
            print(f"{ns}: flow {flow} -> {ok} OK / {blocked} BLOCKED "
                  f"(3-QPS budget enforced by its owning pod)")
            assert (ok, blocked) == (3, 3), (ns, ok, blocked)
        # a flow the routing tables don't know degrades cleanly, no pod hit
        r = router.request_token(999)
        print(f"unrouted flow 999 -> {r.status.name}")
        assert r.status == TokenStatus.NO_RULE_EXISTS
        router.close()
    finally:
        for proc, _, _ in pods.values():
            proc.terminate()
        for proc, _, _ in pods.values():
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--pod":
        pod_main(sys.argv[2], sys.argv[3])
    else:
        main()
