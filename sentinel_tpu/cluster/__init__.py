"""Cluster flow control: token client/server (analog of ``sentinel-cluster``).

- ``protocol``: binary wire codec (5 request types, length-prefixed frames —
  the shape of ``sentinel-cluster-common-default``'s netty codec).
- ``token_service``: the ``TokenService`` SPI and its engine-backed default
  (``DefaultTokenService.java:36`` analog whose decision path is the jitted
  ``sentinel_tpu.engine.decide`` kernel).
- ``server``: asyncio transport + micro-batcher (``NettyTransportServer``
  analog; the batcher is the host front door that turns the 20ms RPC budget
  into ≤~1ms device batches).
- ``client``: sync token client with xid-correlated responses, timeout and
  reconnect (``DefaultClusterTokenClient``/``NettyTransportClient`` analog).
- ``api``: process-global cluster state (CLIENT/SERVER/OFF) consumed by the
  local flow checker's cluster branch (``ClusterStateManager`` analog).

Re-exports are LAZY (PEP 562): importing a jax-free submodule (``protocol``,
``connection``) must not pull the jax-backed service stack — socket-only
processes (bench load clients, the ASan fuzz harness, sidecars that only
speak the wire format) depend on that boundary.
"""

_EXPORTS = {
    "TokenResult": "sentinel_tpu.cluster.token_service",
    "TokenService": "sentinel_tpu.cluster.token_service",
    "DefaultTokenService": "sentinel_tpu.cluster.token_service",
    "ConcurrentFlowRule": "sentinel_tpu.cluster.concurrent",
    "ConcurrentPlane": "sentinel_tpu.cluster.concurrent",
    "ClusterMode": "sentinel_tpu.cluster.api",
    "get_mode": "sentinel_tpu.cluster.api",
    "set_client": "sentinel_tpu.cluster.api",
    "set_embedded_server": "sentinel_tpu.cluster.api",
    "set_mode": "sentinel_tpu.cluster.api",
    "ConnectionManager": "sentinel_tpu.cluster.connection",
    "NamespaceAssignment": "sentinel_tpu.cluster.namespaces",
    "aggregate_snapshots": "sentinel_tpu.cluster.namespaces",
    "flow_namespaces": "sentinel_tpu.cluster.namespaces",
    "partition_rules": "sentinel_tpu.cluster.namespaces",
    "RoutingTokenClient": "sentinel_tpu.cluster.routing",
    "MoveCoordinator": "sentinel_tpu.cluster.rebalance",
    "MoveTarget": "sentinel_tpu.cluster.rebalance",
    "ShardMap": "sentinel_tpu.cluster.rebalance",
    "ShardMapPublisher": "sentinel_tpu.cluster.rebalance",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(
            f"module 'sentinel_tpu.cluster' has no attribute {name!r}"
        )
    import importlib

    return getattr(importlib.import_module(mod), name)


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
