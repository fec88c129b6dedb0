"""Cluster pacing: SHOULD_WAIT + wait-ms instead of blocks, and the
client's opt-in sleep-and-admit.

reference: ``PaceFlowDemo.java`` (``RuleConstant.CONTROL_BEHAVIOR_RATE_
LIMITER``) — but the leaky bucket lives cluster-side as a per-flow
``latest_passed_time`` tensor column (docs/SHAPING.md): a burst against
the token server comes back as OK for the first request and SHOULD_WAIT
with an assigned wait for the rest, spaced 1000/count ms apart. The wire
protocol already carries ``wait_ms``, and ``TokenClient(wait_and_admit=
True)`` turns those verdicts into delayed OKs by sleeping out the assigned
wait client-side — the whole burst passes, paced, with zero rejects.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from sentinel_tpu.cluster.client import TokenClient
from sentinel_tpu.cluster.server import TokenServer
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig, TokenStatus
from sentinel_tpu.engine.rules import ControlBehavior, ThresholdMode

FLOW = 302
NAMES = {
    int(TokenStatus.OK): "OK",
    int(TokenStatus.SHOULD_WAIT): "SHOULD_WAIT",
    int(TokenStatus.BLOCKED): "BLOCKED",
}


def main() -> None:
    svc = DefaultTokenService(
        EngineConfig(max_flows=16, max_namespaces=4, batch_size=64)
    )
    # count=10 → one pass every 100ms; queue caps at 600ms of waits
    svc.load_rules([
        ClusterFlowRule(
            FLOW, 10.0, ThresholdMode.GLOBAL,
            control_behavior=ControlBehavior.RATE_LIMITER,
            max_queueing_time_ms=600,
        )
    ])
    server = TokenServer(svc, port=0, metrics_port=0)
    server.start()
    print(f"token server on :{server.port} — flow {FLOW} paced at 10/s "
          f"(100ms spacing, 600ms max queue)")

    raw = TokenClient("127.0.0.1", server.port, timeout_ms=2000)
    pacer = TokenClient("127.0.0.1", server.port, timeout_ms=2000,
                        wait_and_admit=True)
    try:
        print("\nburst of 5 without wait_and_admit (the raw verdicts):")
        for i in range(5):
            r = raw.request_token(FLOW)
            print(f"  req {i}: {NAMES.get(r.status, r.status)}"
                  + (f" wait={r.wait_ms}ms" if r.wait_ms else ""))

        time.sleep(1.0)  # let the first burst's schedule drain

        print("\nburst of 5 with wait_and_admit=True (sleep out the "
              "assigned wait, then admit):")
        t0 = time.monotonic()
        for i in range(5):
            r = pacer.request_token(FLOW)
            dt = (time.monotonic() - t0) * 1000.0
            print(f"  req {i}: {NAMES.get(r.status, r.status)} "
                  f"at t={dt:5.0f}ms"
                  + (f" (slept {r.wait_ms}ms)" if r.wait_ms else ""))
        total = (time.monotonic() - t0) * 1000.0
        print(f"whole burst admitted, paced over ~{total:.0f}ms "
              f"(≈ 4 × 100ms spacing) — zero rejects")
    finally:
        raw.close()
        pacer.close()
        server.stop()


if __name__ == "__main__":
    main()
