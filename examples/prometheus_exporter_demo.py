"""Prometheus scrape endpoint over live guarded traffic.

reference: ``sentinel-metric-exporter`` (JMX MBeans per resource) — the
Python-ecosystem analog is a pull-based scrape endpoint rendering straight
off the live ClusterNode windows. Besides the per-resource QPS gauges shown
here, the same body carries cumulative ``sentinel_pass_total`` /
``sentinel_block_total`` counters and the ``sentinel_server_*`` token-server
pipeline series — the full reference is ``docs/OBSERVABILITY.md``.
"""

import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from sentinel_tpu.local import BlockException
from sentinel_tpu.local.flow import FlowRule, FlowRuleManager
from sentinel_tpu.local.sph import entry
from sentinel_tpu.metrics.exporter import PrometheusExporter


def main() -> None:
    FlowRuleManager.load_rules([FlowRule(resource="GET:/orders", count=5)])
    exporter = PrometheusExporter(host="127.0.0.1", port=0).start()
    try:
        passed = blocked = 0
        for _ in range(9):
            try:
                with entry("GET:/orders"):
                    passed += 1
            except BlockException:
                blocked += 1
        with urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/metrics", timeout=5
        ) as rsp:
            text = rsp.read().decode()
        wanted = [
            line for line in text.splitlines()
            if "GET:/orders" in line and (
                "pass_qps" in line or "block_qps" in line
                or "pass_total" in line or "block_total" in line
            )
        ]
        print(f"served {passed} / blocked {blocked}; scrape says:")
        for line in wanted:
            print(" ", line)
        assert any("sentinel_pass_qps" in w for w in wanted)
        assert any("sentinel_block_qps" in w for w in wanted)
        # cumulative counters ride the same scrape (rate() these in PromQL
        # instead of trusting the instantaneous QPS gauges)
        assert any("sentinel_pass_total" in w for w in wanted)
    finally:
        exporter.stop()
        FlowRuleManager.load_rules([])


if __name__ == "__main__":
    main()
