"""Two-pod namespace partitioning: tier-2 (DCN) scale-out.

One TPU pod serves one namespace partition (tier 1: the pod's chips shard
the flow axis over ICI — see ``parallel/sharding.py``); namespaces partition
ACROSS pods host-side (tier 2), so the fleet scales beyond a single pod
without any cross-pod coordination on the hot path. This demo runs two
"pods" as two token servers in one process, routes by namespace through
``RoutingTokenClient``, then MOVES a namespace between pods live — in-flight
traffic keeps flowing, budgets stay enforced by the new owner.

reference shape: assignment config of ``sentinel-cluster`` (one token server
per namespace group); the partitioning itself is a TPU-build extension
(SURVEY.md §7.5).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from sentinel_tpu.cluster.namespaces import NamespaceAssignment, partition_rules
from sentinel_tpu.cluster.routing import RoutingTokenClient
from sentinel_tpu.cluster.server import TokenServer
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
from sentinel_tpu.engine.rules import ThresholdMode


def main() -> None:
    # flows 1xx live in namespace "payments", flows 2xx in "search"
    rules = [
        ClusterFlowRule(flow_id=101, count=20.0, mode=ThresholdMode.GLOBAL,
                        namespace="payments"),
        ClusterFlowRule(flow_id=201, count=40.0, mode=ThresholdMode.GLOBAL,
                        namespace="search"),
    ]
    assignment = NamespaceAssignment({"payments": "pod0", "search": "pod1"})

    # one token server per pod, each loading ONLY its partition's rules
    by_pod = partition_rules(rules, assignment)
    pods = {}
    cfg = EngineConfig(max_flows=64, max_namespaces=4, batch_size=128)
    for pod_id in ("pod0", "pod1"):
        svc = DefaultTokenService(cfg)
        svc.load_rules(by_pod.get(pod_id, []))
        server = TokenServer(svc, port=0)
        server.start()
        pods[pod_id] = server
        print(f"{pod_id}: token server on :{server.port} serving "
              f"{assignment.namespaces_of(pod_id)}")

    namespace_of = {r.flow_id: r.namespace for r in rules}
    router = RoutingTokenClient(
        timeout_ms=2000,
        namespace_of=namespace_of,
        pod_of=assignment.snapshot(),
        endpoints={p: ("127.0.0.1", s.port) for p, s in pods.items()},
    )
    try:
        granted = {101: 0, 201: 0}
        for _ in range(60):
            for fid in (101, 201):
                if router.request_token(fid).ok:
                    granted[fid] += 1
        print(f"60 asks each: payments flow 101 granted {granted[101]} "
              f"(budget 20), search flow 201 granted {granted[201]} "
              f"(budget 40) — different pods, independent budgets")

        # live re-partition: move "search" onto pod0 (e.g. pod1 drains for
        # maintenance). The new owner loads the namespace's rules; the
        # router re-points; counters start fresh on the new owner (counters
        # are ephemeral — same stance as the reference on server failover).
        assignment.assign("search", "pod0")
        pods["pod0"].service.load_namespace_rules(
            "search", [r for r in rules if r.namespace == "search"]
        )
        router.update(pod_of=assignment.snapshot())
        moved = sum(router.request_token(201).ok for _ in range(60))
        print(f"after moving 'search' to pod0: granted {moved} of 60 "
              f"(fresh 40-budget on the new owner) — traffic never stopped")
    finally:
        router.close()
        for server in pods.values():
            server.stop()


if __name__ == "__main__":
    main()
