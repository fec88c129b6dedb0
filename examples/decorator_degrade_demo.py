"""@sentinel_resource + circuit breaker: annotation-style degradation.

The ``sentinel-demo-annotation-spring-aop`` × ``sentinel-demo-degrade``
combination (``SentinelResourceAspect.java:36-68`` dispatching to
``fallback``/``blockHandler``, ``ExceptionCircuitBreaker.java:35`` doing the
failure detection): a flaky downstream call is guarded by the decorator;
its error ratio trips the breaker; while OPEN, calls short-circuit into the
fallback without touching the downstream; after the recovery window one
probe call closes the breaker again.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from sentinel_tpu.adapters import sentinel_resource
from sentinel_tpu.core import clock as clock_mod
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.local.degrade import (
    DegradeGrade,
    DegradeRule,
    DegradeRuleManager,
    clear_state_change_observers,
    register_state_change_observer,
)

DOWNSTREAM_CALLS = {"n": 0}
HEALTHY = {"ok": False}


def quote_fallback(symbol, ex=None):
    return f"{symbol}: cached quote (fallback, {type(ex).__name__})"


@sentinel_resource("quote_service", fallback=quote_fallback)
def get_quote(symbol):
    DOWNSTREAM_CALLS["n"] += 1
    if not HEALTHY["ok"]:
        raise ConnectionError("downstream quote service down")
    return f"{symbol}: 42.00"


def main() -> None:
    clock = ManualClock()
    prev = clock_mod.set_clock(clock)
    register_state_change_observer(
        lambda res, frm, to, rule: print(f"  [observer] {res}: {frm.name} -> {to.name}")
    )
    try:
        DegradeRuleManager.load_rules([
            DegradeRule(
                resource="quote_service",
                grade=DegradeGrade.ERROR_RATIO,
                count=0.5,  # open at 50% errors
                min_request_amount=5,
                stat_interval_ms=1000,
                time_window_sec=2,  # recovery timeout
            )
        ])
        clock.set_ms(10_000)

        print("downstream down — errors fall through to the fallback:")
        for _ in range(6):
            print(" ", get_quote("TPU"))
            clock.advance(10)

        print("breaker is OPEN — calls short-circuit (downstream untouched):")
        before = DOWNSTREAM_CALLS["n"]
        for _ in range(3):
            print(" ", get_quote("TPU"))
            clock.advance(10)
        assert DOWNSTREAM_CALLS["n"] == before, "OPEN must not touch downstream"

        print("downstream recovers; after the 2s window one probe closes it:")
        HEALTHY["ok"] = True
        clock.advance(2_100)
        print(" ", get_quote("TPU"))  # HALF_OPEN probe succeeds -> CLOSED
        print(" ", get_quote("TPU"))  # normal traffic again
        assert DOWNSTREAM_CALLS["n"] == before + 2
    finally:
        clear_state_change_observers()
        DegradeRuleManager.load_rules([])
        clock_mod.set_clock(prev)
    print("decorator + degrade demo OK")


if __name__ == "__main__":
    main()
