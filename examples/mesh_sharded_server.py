"""One token-server pod whose chips decide TOGETHER: tier-1 (ICI) sharding.

The flow axis of the engine state and rule table shards across the pod's
devices (here: an 8-device virtual CPU mesh standing in for a v5e-8);
``shard_map`` + psums stitch each batch's verdicts across shards inside one
jitted step (``parallel/sharding.py``), and the TCP front door serves that
sharded step exactly like a single-chip one — clients cannot tell.

This demo exercises the REAL serving path, not a demo fork of it: the
mesh-backed service runs the same donating sharded step, greedy fusion
ladder (oversized pulls fold into one ``lax.scan``-of-``shard_map`` device
dispatch), host prep, and staging freelists as production serving — the
mesh only changes the step function (``docs/PERF.md`` "Pod serving"). The
same layout snapshots and delta-replicates to standbys of any mesh shape
(``docs/CLUSTER_HA.md``).

reference shape: one embedded token server owning its namespace's flows
(``DefaultTokenService.java:36-97`` + ``NettyTransportServer.java:73-101``);
the intra-pod flow-axis sharding is the TPU-build extension (SURVEY.md §7.5,
tier 1 — tier 2, namespace partitioning ACROSS pods, is
``namespace_partition_demo.py``).

Run: ``python examples/mesh_sharded_server.py`` (pure CPU, ~20 s).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 8 virtual CPU devices must be requested before the first backend creation
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from sentinel_tpu.cluster.client import TokenClient  # noqa: E402
from sentinel_tpu.cluster.server import TokenServer  # noqa: E402
from sentinel_tpu.cluster.token_service import DefaultTokenService  # noqa: E402
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig  # noqa: E402
from sentinel_tpu.engine.rules import ThresholdMode  # noqa: E402
from sentinel_tpu.parallel import make_flow_mesh  # noqa: E402


def main() -> None:
    mesh = make_flow_mesh()
    print(f"pod mesh: {len(mesh.devices.flat)} devices, axes {mesh.axis_names}")

    # 64 flow slots shard 8 per device; batch verdicts are psum-stitched
    config = EngineConfig(max_flows=64, max_namespaces=4, batch_size=64)
    service = DefaultTokenService(config, mesh=mesh, serve_buckets=(64,))
    service.load_rules(
        [
            ClusterFlowRule(flow_id=i, count=3.0, mode=ThresholdMode.GLOBAL)
            for i in range(16)
        ]
    )
    service.warmup()  # compile the sharded step outside the serving window

    n_dev = len(mesh.devices.flat)
    print(
        f"flow window tensor: {n_dev} shards of "
        f"{config.max_flows // n_dev} flow slots each (flow axis over ICI)"
    )

    server = TokenServer(service, host="127.0.0.1", port=0, max_batch=64)
    server.start()
    client = TokenClient("127.0.0.1", server.port, timeout_ms=5000)
    try:
        # 5 requests for flow 1 (budget 3/s) through the real front door:
        # the owning shard admits exactly 3, psums carry the verdicts back
        res = client.request_batch_arrays(np.full(5, 1, np.int64))
        assert res is not None, "no response from the pod"
        statuses = res[0]
        ok = int((statuses == 0).sum())
        blocked = int((statuses == 1).sum())
        print(f"flow 1 (budget 3/s): {ok} OK, {blocked} BLOCKED over TCP")
        assert (ok, blocked) == (3, 2), statuses
    finally:
        client.close()
        server.stop()
        service.close()
    print("mesh-sharded pod served and enforced over the wire — OK")


if __name__ == "__main__":
    main()
