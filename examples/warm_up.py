"""Warm-up (cold start) traffic shaping.

reference: ``WarmUpFlowDemo.java`` / ``WarmUpController.java:64-170``.

Part 1 guards real traffic: a cold system admits only count/coldFactor.
Part 2 drives the controller with sustained warning-rate readings (the
reference's own ``WarmUpControllerTest`` pattern — under single-threaded
deterministic load the drain never triggers, in the reference too, because
admissions cluster into one bucket per second) and prints the admissible-QPS
curve as the token bucket drains from cold to warm.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from sentinel_tpu.core import clock as clock_mod
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.local import BlockException
from sentinel_tpu.local.flow import (
    ControlBehavior,
    FlowRule,
    FlowRuleManager,
    WarmUpController,
)
from sentinel_tpu.local.sph import entry


class _Node:
    """Minimal stat stub for driving the controller directly."""

    def __init__(self):
        self.cur_pass = 0.0
        self.prev = 0.0

    def pass_qps(self, now=None):
        return self.cur_pass

    def previous_pass_qps(self, now=None):
        return self.prev


def main() -> None:
    clock = ManualClock()
    prev_clock = clock_mod.set_clock(clock)
    try:
        # --- part 1: cold cap on real entries (count=100, coldFactor=3) ---
        FlowRuleManager.load_rules([
            FlowRule(
                resource="warm",
                count=100,
                control_behavior=ControlBehavior.WARM_UP,
                warm_up_period_sec=5,
            )
        ])
        clock.set_ms(10_000)
        passed = 0
        for _ in range(200):
            try:
                with entry("warm"):
                    passed += 1
            except BlockException:
                pass
            clock.sleep(5)
        print(f"cold system, offered 200/s: admitted {passed} "
              f"(≈ count/coldFactor = 100/3)")

        # --- part 2: the warm-up curve under sustained warning-rate load ---
        ctl = WarmUpController(count=100, warm_up_period_sec=5)
        node = _Node()
        clock.set_ms(100_000)
        print("\nsustained load at the admissible rate (tokens drain):")
        for second in range(9):
            # measure this second's admissible rate, then feed it back as the
            # measured pass qps of the next sync (sustained saturation)
            node.cur_pass = 0.0
            admissible = 0
            for _ in range(150):
                if ctl.can_pass(node, 1):
                    node.cur_pass += 1
                    admissible += 1
            print(f"  t={second}s admissible={admissible}/s "
                  f"stored_tokens={ctl._stored_tokens:.0f}")
            node.prev = float(admissible + 1)  # concurrency jitter: ≥ warning
            clock.sleep(1_000)
        print("tokens fell below the warning line → full rate (count=100)")
    finally:
        FlowRuleManager.reset_for_tests()
        clock_mod.set_clock(prev_clock)


if __name__ == "__main__":
    main()
