"""Sweep (batch, chain) shapes of the headline decide kernel on the live
backend and print decisions/s per shape — picks the bench.py ATTEMPTS shape
with data instead of folklore. One process, shapes run sequentially, JSON
line per shape so a timeout loses only the tail.

Usage: python benchmarks/shape_sweep.py [batch,chain ...]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sentinel_tpu.core.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    from sentinel_tpu.engine import (
        ClusterFlowRule,
        EngineConfig,
        TokenStatus,
        build_rule_table,
        make_batch,
        make_state,
    )
    from sentinel_tpu.engine.decide import _decide_core
    from sentinel_tpu.engine.rules import ThresholdMode

    shapes = [
        tuple(int(x) for x in arg.split(","))
        for arg in sys.argv[1:]
    ] or [(16384, 64), (32768, 64), (65536, 32), (8192, 128)]

    n_flows = 100_000
    rules = [
        ClusterFlowRule(flow_id=i, count=100.0 + (i % 100),
                        mode=ThresholdMode.GLOBAL, namespace=f"ns{i % 64}")
        for i in range(n_flows)
    ]
    rng = np.random.default_rng(0)
    dev = jax.devices()[0]

    for batch, chain in shapes:
        config = EngineConfig(
            max_flows=n_flows, max_namespaces=64, batch_size=batch
        )
        table, _ = build_rule_table(config, rules, ns_max_qps=1e9)
        state = make_state(config)

        def chained(state, stacked, now0):
            def body(carry, xs):
                st, now = carry
                st, verdicts = _decide_core(
                    config, st, table, xs, now, grouped=True, uniform=True
                )
                return (st, now + 1), verdicts.status

            (state, _), statuses = jax.lax.scan(body, (state, now0), stacked)
            return state, statuses

        step = jax.jit(chained, donate_argnums=(0,))
        batches = []
        for _ in range(chain):
            slots = np.sort(
                rng.integers(0, n_flows, size=batch)
            ).tolist()
            batches.append(make_batch(config, slots))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)

        now = 10_000
        t0 = time.perf_counter()
        state, statuses = step(state, stacked, jnp.int32(now))
        jax.block_until_ready(statuses)
        compile_s = time.perf_counter() - t0
        # over the whole [chain, batch] status array: budgets drain across
        # the scan, so batch 0 alone would overstate admission
        ok = float((np.asarray(statuses) == TokenStatus.OK).mean())

        lat = []
        for _ in range(3):
            now += chain
            t0 = time.perf_counter()
            state, statuses = step(state, stacked, jnp.int32(now))
            jax.block_until_ready(statuses)
            lat.append(time.perf_counter() - t0)
        best = min(lat)
        print(json.dumps({
            "batch": batch, "chain": chain,
            "decisions_per_sec": round(chain * batch / best),
            "per_batch_ms": round(best / chain * 1e3, 3),
            "compile_s": round(compile_s, 1),
            "ok_frac": round(ok, 3),
            "backend": dev.platform,
        }), flush=True)


if __name__ == "__main__":
    main()
