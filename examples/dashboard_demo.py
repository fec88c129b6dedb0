"""Full control plane in one process: a guarded app with a command center +
heartbeat, and a dashboard that discovers it, pulls metrics, and pushes a
rule to it.

reference: ``sentinel-dashboard`` + ``sentinel-transport`` wiring.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import json
import time
import urllib.request

from sentinel_tpu.dashboard.server import DashboardServer
from sentinel_tpu.local import BlockException
from sentinel_tpu.local.flow import FlowRuleManager
from sentinel_tpu.local.sph import entry
from sentinel_tpu.metrics.log import MetricTimer
from sentinel_tpu.transport.command import CommandCenter
from sentinel_tpu.transport.heartbeat import HeartbeatSender


def main() -> None:
    dash = DashboardServer(port=0).start()
    cc = CommandCenter(port=0).start()
    hb = HeartbeatSender(
        dashboard_addrs=[f"127.0.0.1:{dash.port}"],
        command_port=cc.port,
        interval_ms=500,
        client_ip="127.0.0.1",
    ).start()
    mt = MetricTimer(interval_s=0.5).start()
    try:
        print(f"dashboard :{dash.port}  command center :{cc.port}")
        # drive some traffic (unguarded by rules yet)
        for _ in range(60):
            try:
                with entry("demoApi"):
                    pass
            except BlockException:
                pass
        time.sleep(2.5)  # heartbeat registers; metric log flushes; fetch runs

        apps = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{dash.port}/apps", timeout=3))
        print("dashboard discovered:",
              [(a["name"], len(a["machines"])) for a in apps])

        # push a flow rule through the dashboard to the app
        app_name = apps[0]["name"]
        req = urllib.request.Request(
            f"http://127.0.0.1:{dash.port}/rules?app={app_name}&type=flow",
            data=json.dumps([{"resource": "demoApi", "count": 3}]).encode(),
            headers={"Content-Type": "application/json"},
        )
        print("rule push:", json.load(urllib.request.urlopen(req, timeout=3)))
        ok = 0
        for _ in range(10):
            try:
                with entry("demoApi"):
                    ok += 1
            except BlockException:
                pass
        print(f"after pushed rule count=3: admitted {ok}/10")
    finally:
        mt.stop()
        hb.stop()
        cc.stop()
        dash.stop()
        FlowRuleManager.reset_for_tests()


if __name__ == "__main__":
    main()
