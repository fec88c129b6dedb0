"""Datasource-wired cluster: files drive mode, assignment, and rules.

The ``DemoClusterInitFunc.java:48-70`` idiom without a dashboard in the
loop: one watched file holds the cluster map (who is the token server), one
holds the cluster flow rules. Editing the rule file re-budgets the fleet
live; the mode/assignment properties come from the same datasource layer
the Nacos/etcd/… backends feed in production.

Wiring (all property-driven, no HTTP commands):

- ``cluster_map.json``  → ``register_cluster_mode_property``  (this process
  promotes itself to an embedded token server, ``ClusterStateManager``)
- ``cluster_map.json``  → ``register_client_assign_property`` (a client
  re-points at the mapped server, ``ClusterClientConfigManager``)
- ``flow_rules.json``   → ``DefaultTokenService.load_namespace_rules``
  (the ``registerClusterRuleSupplier`` analog: rules per namespace follow
  the datasource)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import json
import socket
import tempfile
import time

from sentinel_tpu.cluster import assign as cluster_assign
from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.datasource.converters import cluster_flow_rules_from_json
from sentinel_tpu.datasource.file import FileRefreshableDataSource
from sentinel_tpu.transport import handlers as H

FLOW_ID = 7001


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _admitted(client: TokenClient, n: int) -> int:
    return sum(client.request_token(FLOW_ID).ok for _ in range(n))


def main() -> None:
    workdir = tempfile.mkdtemp(prefix="sentinel-cluster-ds-")
    map_path = os.path.join(workdir, "cluster_map.json")
    rules_path = os.path.join(workdir, "flow_rules.json")
    port = _free_port()

    # the "cluster map" a config service would hold: one entry saying who
    # serves tokens (ClusterGroupEntity shape, trimmed)
    with open(map_path, "w") as f:
        json.dump({"mode": 1, "tokenPort": port}, f)
    with open(rules_path, "w") as f:
        json.dump([{"flowId": FLOW_ID, "count": 10, "thresholdType": 1}], f)

    # mode follows the map file → this process promotes itself to server
    mode_ds = FileRefreshableDataSource(
        map_path, converter=json.loads, refresh_interval_s=0.2
    ).start()
    cluster_assign.register_cluster_mode_property(mode_ds.property)
    for _ in range(50):
        if H._EMBEDDED_SERVER["server"] is not None:
            break
        time.sleep(0.1)
    server = H._EMBEDDED_SERVER["server"]
    assert server is not None, "mode datasource did not promote the server"
    print(f"promoted to embedded token server on :{server.port} (from file)")

    # rules follow the rule file → the server's namespace rule supplier
    rules_ds = FileRefreshableDataSource(
        rules_path, converter=cluster_flow_rules_from_json,
        refresh_interval_s=0.2,
    ).start()
    rules_ds.property.listen(
        lambda rules: server.service.load_namespace_rules(
            "default", rules or []
        )
    )

    client = TokenClient("127.0.0.1", server.port, timeout_ms=2000)
    try:
        got = _admitted(client, 20)
        print(f"budget 10/s: {got}/20 admitted")
        assert got == 10, got

        # a config push: edit the rule file, fleet re-budgets itself
        with open(rules_path, "w") as f:
            json.dump([{"flowId": FLOW_ID, "count": 3, "thresholdType": 1}], f)
        time.sleep(0.6)  # refresh interval + settle
        time.sleep(1.1)  # let the 1s metric window roll past the old grants
        got = _admitted(client, 20)
        print(f"budget  3/s: {got}/20 admitted after editing flow_rules.json")
        assert got == 3, got
    finally:
        client.close()
        rules_ds.close()
        mode_ds.close()
        H.apply_cluster_mode(-1)
    print("datasource-driven cluster demo OK")


if __name__ == "__main__":
    main()
