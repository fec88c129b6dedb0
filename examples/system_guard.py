"""System-adaptive (BBR-style) inbound protection.

reference: ``SystemGuardDemo.java`` / ``SystemRuleManager.java:290-340`` —
a global qps ceiling over ALL inbound traffic, independent of per-resource
rules.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from sentinel_tpu.core import clock as clock_mod
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.local import BlockException, EntryType
from sentinel_tpu.local.sph import entry
from sentinel_tpu.local.system_adaptive import SystemRule, SystemRuleManager


def main() -> None:
    clock = ManualClock()
    prev = clock_mod.set_clock(clock)
    try:
        SystemRuleManager.load_rules([SystemRule(qps=50)])
        clock.set_ms(10_000)
        passed = blocked = 0
        for _ in range(120):
            try:
                with entry("anyInboundApi", EntryType.IN):
                    passed += 1
            except BlockException:
                blocked += 1
        print(f"offered 120 inbound this second: pass={passed} block={blocked}")
        print("(global system qps=50 guards every IN entry)")
    finally:
        SystemRuleManager.reset_for_tests()
        clock_mod.set_clock(prev)


if __name__ == "__main__":
    main()
