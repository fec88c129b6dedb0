"""The system under test, built from a deployment file through the program's
public doors: ``DefaultTokenService`` + ``NativeTokenServer`` with the
server's own defaults for overload, age shed and fusion. Which rules are
loaded, and which constructor arguments the file states beyond the engine's,
is the deployment's family's (``service_args``, ``load_rules``); the door is
not a family's.
"""

from __future__ import annotations

import time


class Built:
    def __init__(self, service, server, parts: dict, mesh_chips: int):
        self.service, self.server = service, server
        self.parts, self.mesh_chips = parts, mesh_chips

    def close(self) -> None:
        try:
            self.server.stop()
        finally:
            self.service.close()


def build(dep, devices, say, wrap_service=None) -> Built:
    """Load the rules, start the door (which runs ``warmup()``). ``devices``
    are the JAX devices the cell may use. ``wrap_service`` lets a test put a
    broken service in the timed path's place."""
    from sentinel_tpu.cluster.server_native import NativeTokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import EngineConfig
    from sentinel_tpu.native import lib as native_lib

    parts = {}
    t = time.monotonic()
    native_lib.require()  # builds the door's library when missing or stale
    parts["native_lib_s"] = time.monotonic() - t

    spec = dep.spec
    e = spec["engine"]
    config = EngineConfig(
        max_flows=int(e["max_flows"]), max_namespaces=int(e["max_namespaces"]),
        batch_size=int(e["batch_size"]), bucket_ms=int(e["bucket_ms"]),
        n_buckets=int(e["n_buckets"]),
    )
    mesh = None
    mesh_chips = int(spec.get("mesh_chips", 0))
    if mesh_chips:
        from sentinel_tpu.parallel import make_flow_mesh

        mesh = make_flow_mesh(list(devices)[:mesh_chips])
    service = DefaultTokenService(
        config, serve_buckets=tuple(spec["serve_buckets"]),
        fuse_depths=tuple(spec["fuse_depths"]), mesh=mesh,
        **dep.family.service_args(dep),
    )
    t = time.monotonic()
    n_rules = dep.family.load_rules(service, dep)
    parts["rule_load_s"] = time.monotonic() - t
    door = spec["door"]
    if door["kind"] != "native_tcp":
        raise ValueError(f"door {door['kind']!r} is not built here yet")
    served = wrap_service(service) if wrap_service is not None else service
    server = NativeTokenServer(served, host="127.0.0.1", port=0,
                               max_batch=int(door["max_batch"]))
    t = time.monotonic()
    server.start()  # runs service.warmup(): every serve bucket compiles here
    parts["warmup_s"] = time.monotonic() - t
    say(f"server up on port {server.port}: {n_rules} rules, "
        f"mesh_chips {mesh_chips}, parts "
        + ", ".join(f"{k} {v:.2f}" for k, v in parts.items()))
    return Built(service, server, parts, mesh_chips)
