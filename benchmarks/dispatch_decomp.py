"""Decompose measured step time into per-dispatch overhead vs true device
step time.

Every dispatch pays a fixed host-to-device overhead ``o``. A
single-iteration-count measurement of a chained scan folds it into the
per-step quotient:

    measured(iters) = (o + iters * d) / iters

Timing the SAME chained kernel at two iteration counts separates the two:

    d  = (t(hi) - t(lo)) / (hi - lo)            # true per-step device time
    o  = t(lo) - lo * d                         # per-dispatch overhead

The slope ``d`` is what pipelined steps pay each, so the SLO projection in
bench.py uses the slope, while the intercept is reported alongside.
Prints ONE JSON line; safe to run standalone on any backend.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def measure(n_flows: int = 100_000, buckets=(64, 1024, 4096, 16384),
            iters_lo: int = 100, iters_hi: int = 400, reps: int = 3) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sentinel_tpu.core.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    from sentinel_tpu.engine import (
        ClusterFlowRule,
        EngineConfig,
        build_rule_table,
        make_batch,
        make_state,
    )
    from sentinel_tpu.engine.decide import _decide_core
    from sentinel_tpu.engine.rules import ThresholdMode

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    config = EngineConfig(max_flows=n_flows, max_namespaces=64, batch_size=64)
    rules = [
        ClusterFlowRule(flow_id=i, count=100.0 + (i % 100),
                        mode=ThresholdMode.GLOBAL, namespace=f"ns{i % 64}")
        for i in range(n_flows)
    ]
    table, _ = build_rule_table(config, rules, ns_max_qps=1e9)

    # per-dispatch overhead floor on a trivial kernel (scalar add): the
    # pure dispatch cost with no kernel work to speak of
    one = jnp.float32(1.0)
    triv = jax.jit(lambda x: x + 1.0)
    jax.block_until_ready(triv(one))
    triv_ms = []
    for _ in range(10):
        t0 = time.perf_counter()
        jax.block_until_ready(triv(one))
        triv_ms.append((time.perf_counter() - t0) * 1e3)
    triv_ms.sort()

    out = {
        "backend": dev.platform,
        "device": str(dev),
        "trivial_dispatch_ms": {
            "p50": round(triv_ms[len(triv_ms) // 2], 3),
            "min": round(triv_ms[0], 3),
        },
        "iters": [iters_lo, iters_hi],
        "per_bucket": {},
    }

    for bucket in buckets:
        cfgb = config._replace(batch_size=bucket)
        slots = np.sort(rng.integers(0, n_flows, size=bucket)).tolist()
        batch_b = jax.tree.map(jnp.asarray, make_batch(cfgb, slots))

        def chained(iters):
            def run(state, batch, now0):
                def body(st, t):
                    st, verdicts = _decide_core(
                        cfgb, st, table, batch, t, grouped=True, uniform=True
                    )
                    return st, verdicts.status[0]

                ts = now0 + jnp.arange(iters, dtype=jnp.int32)
                return jax.lax.scan(body, state, ts)

            step = jax.jit(run)
            out_w = step(make_state(config), batch_b, jnp.int32(10_000))
            jax.block_until_ready(out_w)
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(
                    step(make_state(config), batch_b, jnp.int32(10_000))
                )
                best = min(best, time.perf_counter() - t0)
            return best * 1e3  # ms per dispatch

        t_lo = chained(iters_lo)
        t_hi = chained(iters_hi)
        d_ms = (t_hi - t_lo) / (iters_hi - iters_lo)
        row = {"naive_step_ms_at_lo": round(t_lo / iters_lo, 4)}
        if d_ms > 0:
            row["step_ms_slope"] = round(d_ms, 4)
            row["dispatch_overhead_ms"] = round(t_lo - iters_lo * d_ms, 2)
        else:
            # jitter swamped the two-point fit — never publish a negative
            # slope or an overhead exceeding the measured wall time
            row["fit_failed"] = True
        out["per_bucket"][str(bucket)] = row
    return out


def main() -> None:
    doc = measure()
    line = json.dumps(doc)
    print(line, flush=True)
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(
            d, f"decomp-{time.strftime('%Y%m%d-%H%M%S')}.json"), "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
