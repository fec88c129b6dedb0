"""Slow-call-ratio circuit breaker: CLOSED → OPEN → HALF_OPEN → CLOSED.

reference: ``ResponseTimeCircuitBreaker.java:34`` + state machine in
``AbstractCircuitBreaker.java:33-155``. Manual clock makes the recovery
timeout instantaneous.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from sentinel_tpu.core import clock as clock_mod
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.local import BlockException
from sentinel_tpu.local.degrade import (
    DegradeGrade,
    DegradeRule,
    DegradeRuleManager,
    register_state_change_observer,
    clear_state_change_observers,
)
from sentinel_tpu.local.sph import entry


def main() -> None:
    clock = ManualClock()
    prev = clock_mod.set_clock(clock)
    register_state_change_observer(
        lambda res, frm, to, rule: print(f"  [observer] {res}: {frm.name} -> {to.name}")
    )
    try:
        DegradeRuleManager.load_rules([
            DegradeRule(
                resource="api",
                grade=DegradeGrade.SLOW_REQUEST_RATIO,
                count=50,  # calls slower than 50ms are "slow"
                slow_ratio_threshold=0.5,
                min_request_amount=5,
                stat_interval_ms=1000,
                time_window_sec=2,  # recovery timeout
            )
        ])
        clock.set_ms(10_000)

        def call(duration_ms: int) -> str:
            try:
                with entry("api"):
                    clock.sleep(duration_ms)
                return "ok"
            except BlockException:
                return "CUT"

        print("6 slow calls (120ms each):", [call(120) for _ in range(6)])
        print("while OPEN:", [call(1) for _ in range(3)])
        clock.sleep(2_100)  # recovery window elapses
        print("probe after recovery (fast):", call(1), "— breaker closes")
        print("normal traffic:", [call(1) for _ in range(3)])
    finally:
        DegradeRuleManager.reset_for_tests()
        clear_state_change_observers()
        clock_mod.set_clock(prev)


if __name__ == "__main__":
    main()
