"""Hot-parameter flow control: per-value token buckets with a per-item
override for a VIP value.

reference: ``sentinel-demo-parameter-flow-control`` /
``ParamFlowChecker.java:46-190``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from sentinel_tpu.core import clock as clock_mod
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.local import BlockException
from sentinel_tpu.local.param import (
    ParamFlowItem,
    ParamFlowRule,
    ParamFlowRuleManager,
)
from sentinel_tpu.local.sph import entry


def main() -> None:
    clock = ManualClock()
    prev = clock_mod.set_clock(clock)
    try:
        ParamFlowRuleManager.load_rules([
            ParamFlowRule(
                resource="getUser",
                param_idx=0,
                count=2,  # 2 QPS per distinct user id
                items=[ParamFlowItem(object_value="vip", count=10)],
            )
        ])
        clock.set_ms(10_000)
        counts = {}
        for user in ("alice", "bob", "vip") * 12:
            try:
                with entry("getUser", args=(user,)):
                    counts[user] = counts.get(user, 0) + 1
            except BlockException:
                pass
        print(f"admitted this second: {counts}")
        print("(ordinary users capped at 2, the vip item override allows 10)")
    finally:
        ParamFlowRuleManager.reset_for_tests()
        clock_mod.set_clock(prev)


if __name__ == "__main__":
    main()
