"""Leaky-bucket pacing: bursts are smoothed into uniform gaps instead of
rejected.

reference: ``PaceFlowDemo.java`` / ``RateLimiterController.java:46-91``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import time

from sentinel_tpu.local import BlockException
from sentinel_tpu.local.flow import ControlBehavior, FlowRule, FlowRuleManager
from sentinel_tpu.local.sph import entry


def main() -> None:
    FlowRuleManager.load_rules([
        FlowRule(
            resource="paced",
            count=10,  # one pass every ~100ms
            control_behavior=ControlBehavior.RATE_LIMITER,
            max_queueing_time_ms=2_000,
        )
    ])
    t0 = time.time()
    stamps = []
    for i in range(10):  # a burst of 10 arrives at once
        try:
            with entry("paced"):
                stamps.append(time.time() - t0)
        except BlockException:
            print(f"request {i}: queue full, rejected")
    gaps = [round(b - a, 3) for a, b in zip(stamps, stamps[1:])]
    print(f"pass times: {[round(s, 3) for s in stamps]}")
    print(f"gaps: {gaps} (~0.1s each — the burst was paced, not dropped)")
    FlowRuleManager.reset_for_tests()


if __name__ == "__main__":
    main()
