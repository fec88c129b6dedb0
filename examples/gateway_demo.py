"""API-gateway flow control: route rules, a custom API group, and per-client
parameter limiting.

reference: ``sentinel-demo-api-gateway`` (zuul/spring-cloud-gateway demos) —
a route rule paces the whole route, a ``GatewayParamFlowItem`` keys the
budget per client IP, and an ``ApiDefinition`` groups paths under one shared
budget (``GatewayApiMatcherManager`` pick).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from sentinel_tpu.core import clock as clock_mod
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.local import BlockException
from sentinel_tpu.adapters.gateway import (
    DictRequestAdapter,
    GatewayFlowRule,
    GatewayGuard,
    GatewayParamFlowItem,
    GatewayRuleManager,
    ParseStrategy,
    ResourceMode,
)
from sentinel_tpu.adapters.gateway_api import (
    ApiDefinition,
    ApiPathPredicateItem,
    GatewayApiDefinitionManager,
    UrlMatchStrategy,
)


def serve(route: str, path: str, ip: str) -> bool:
    request = DictRequestAdapter(ip=ip)
    try:
        with GatewayGuard(route, request, path=path):
            return True
    except BlockException:
        return False


def main() -> None:
    clock = ManualClock()
    prev = clock_mod.set_clock(clock)
    try:
        clock.set_ms(10_000)
        # every /product/* path shares ONE "product-api" budget
        GatewayApiDefinitionManager.load_api_definitions([
            ApiDefinition(
                "product-api",
                (ApiPathPredicateItem("/product/",
                                      UrlMatchStrategy.PREFIX),),
            )
        ])
        GatewayRuleManager.load_rules([
            # per-client budget on the route: 3 QPS per distinct IP
            GatewayFlowRule(
                resource="shop-route", count=3,
                param_item=GatewayParamFlowItem(
                    parse_strategy=ParseStrategy.CLIENT_IP
                ),
            ),
            # the API group caps all /product/* paths together at 5 QPS
            GatewayFlowRule(
                resource="product-api",
                resource_mode=ResourceMode.CUSTOM_API_NAME, count=5,
            ),
        ])

        per_ip = {}
        for ip in ("10.0.0.1", "10.0.0.2"):
            per_ip[ip] = sum(
                serve("shop-route", "/cart", ip) for _ in range(6)
            )
        print(f"route per-IP budgets: {per_ip} (3 QPS each)")
        assert per_ip == {"10.0.0.1": 3, "10.0.0.2": 3}, per_ip

        clock.advance(1000)
        passed = sum(
            serve("shop-route", f"/product/{i}", f"10.0.1.{i}")
            for i in range(8)
        )
        print(f"product-api group: {passed}/8 passed (5 QPS shared across "
              "paths and IPs)")
        # the route's per-IP budget (3/ip) never binds here — 8 distinct
        # IPs, one request each — so the shared API-group cap is what limits
        assert passed == 5, passed
    finally:
        GatewayRuleManager.reset_for_tests()
        GatewayApiDefinitionManager.reset_for_tests()
        clock_mod.set_clock(prev)


if __name__ == "__main__":
    main()
