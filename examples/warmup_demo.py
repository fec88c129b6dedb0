"""Cluster warm-up: the device engine's cold-start admission curve.

reference: ``WarmUpFlowDemo.java`` — but enforced CLUSTER-side: the warmup
token bucket lives as per-flow tensor columns inside the batched decide
kernel (see docs/SHAPING.md), so every connected client shares ONE
cold-start ramp instead of each warming up privately.

Part 1 drives a cold service and shows the count/coldFactor cap. Part 2
prints the admissible-QPS slope curve straight from the compiled rule
columns — the same numbers the kernel's ``warning_qps`` branch evaluates
as the bucket drains from maxToken down to the warning line.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np  # noqa: E402

from sentinel_tpu.core import clock as clock_mod
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
from sentinel_tpu.engine.rules import ControlBehavior, ThresholdMode

FLOW = 301


def main() -> None:
    clock = ManualClock()
    prev_clock = clock_mod.set_clock(clock)
    try:
        svc = DefaultTokenService(
            EngineConfig(max_flows=16, max_namespaces=4, batch_size=64)
        )
        svc.load_rules([
            ClusterFlowRule(
                FLOW, 100.0, ThresholdMode.GLOBAL,
                control_behavior=ControlBehavior.WARM_UP,
                warm_up_period_sec=10, cold_factor=3,
            )
        ])
        clock.set_ms(10_000)

        # --- part 1: a cold cluster admits count/coldFactor ---------------
        admitted = 0
        for _ in range(200):
            if svc.request_token(FLOW).ok:
                admitted += 1
            clock.sleep(5)
        print(f"cold cluster, offered 200/s: admitted {admitted} "
              f"(≈ count/coldFactor = 100/3)")

        # --- part 2: the slope curve the kernel walks as tokens drain -----
        table = svc._table
        slot = svc._index.slot_of[FLOW]
        cnt = float(np.asarray(table.count)[slot])
        warn = float(np.asarray(table.warning_token)[slot])
        max_tok = float(np.asarray(table.max_token)[slot])
        slope = float(np.asarray(table.slope)[slot])
        print(f"\nrule columns: warningToken={warn:.0f} maxToken={max_tok:.0f}"
              f" slope={slope:.6f}")
        print("admissible QPS as the stored-token bucket drains:")
        for tok in np.linspace(max_tok, warn, 6):
            qps = 1.0 / ((tok - warn) * slope + 1.0 / cnt)
            print(f"  tokens={tok:6.0f}  admissible={qps:5.1f}/s")
        print(f"below the warning line the full count applies: {cnt:.0f}/s")
    finally:
        clock_mod.set_clock(prev_clock)


if __name__ == "__main__":
    main()
