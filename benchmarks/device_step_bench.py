"""Per-bucket device-step time for the serving decide kernel.

Measures what one serving-shape decision step costs ON DEVICE, excluding
host prep and the per-dispatch overhead: K
steps are chained through ``lax.scan`` (state threaded step-to-step, same
data dependency as serving) inside ONE jitted dispatch, so per-step device
time = total / K regardless of dispatch latency.

This is the device component of the serving-latency story: end-to-end
verdict latency on co-located hardware ≈ host path (prep + dispatch +
unpack, ~0.1-0.3 ms measured on the CPU harness) + this number.

Usage: ``python benchmarks/device_step_bench.py [--buckets 64 256 1024]
[--iters 200] [--cpu]``
Prints ONE JSON line and appends a copy under ``benchmarks/results/``.
"""

from __future__ import annotations

import os as _os
import sys as _sys

_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO not in _sys.path:
    _sys.path.insert(0, _REPO)

import argparse
import json
import os
import time


def run(buckets=(64, 256, 1024), iters: int = 200, n_flows: int = 1024) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sentinel_tpu.engine import (
        ClusterFlowRule,
        EngineConfig,
        build_rule_table,
        decide,
        make_batch,
        make_state,
    )
    from sentinel_tpu.engine.rules import ThresholdMode

    config = EngineConfig(
        max_flows=n_flows, max_namespaces=8, batch_size=max(buckets)
    )
    rules = [
        ClusterFlowRule(flow_id=i, count=1e9, mode=ThresholdMode.GLOBAL,
                        namespace=f"ns{i % 8}")
        for i in range(n_flows)
    ]
    table, index = build_rule_table(config, rules, ns_max_qps=1e12)
    rng = np.random.default_rng(0)

    per_bucket = {}
    for bucket in buckets:
        cfg = config._replace(batch_size=bucket)
        slots = rng.integers(0, n_flows, bucket).astype(np.int32)
        batch = make_batch(cfg, np.sort(slots))
        batch = jax.tree_util.tree_map(jnp.asarray, batch)
        state0 = make_state(config)

        @jax.jit
        def chained(state, table, batch):
            def body(carry, t):
                st, _ = decide(
                    cfg, carry, table, batch, t, grouped=True, uniform=True
                )
                return st, ()

            # distinct, increasing timestamps so window math stays realistic
            ts = jnp.arange(1, iters + 1, dtype=jnp.int32)
            state, _ = jax.lax.scan(body, state, ts)
            return state

        out = chained(state0, table, batch)  # compile + warm
        jax.block_until_ready(out)
        reps = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(chained(state0, table, batch))
            reps.append((time.perf_counter() - t0) / iters * 1e3)
        per_bucket[bucket] = {
            "step_ms": round(min(reps), 4),
            "step_ms_med": round(sorted(reps)[len(reps) // 2], 4),
            "decisions_per_sec": round(bucket / (min(reps) / 1e3)),
        }

    return {
        "metric": "device_step_time_per_serve_bucket",
        "value": per_bucket[max(buckets)]["step_ms"],
        "unit": f"ms_per_step_bucket{max(buckets)}",
        "vs_baseline": 1.0,
        "extra": {
            "per_bucket": {str(k): v for k, v in per_bucket.items()},
            "iters_chained": iters,
            "n_flows": n_flows,
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--buckets", type=int, nargs="+", default=[64, 256, 1024])
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    result = run(tuple(args.buckets), args.iters)
    line = json.dumps(result)
    print(line)
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"devstep-{time.strftime('%Y%m%d-%H%M%S')}.json"),
              "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
