"""North-star acceptance bench: publish the BASELINE line or name the
exact bottleneck with a per-stage byte-and-time budget.

The north star (ROADMAP / BASELINE.json): **>= 10M served flow
decisions/s on one v5e-8 across 100k+ resources at p99 < 2 ms**.

This bench measures the serving pipeline stage by stage on whatever host
it runs on, then renders ONE machine-parseable verdict line:

- ``BASELINE {json}`` when the host is real acceptance hardware (TPU
  backend, >= 8 chips) AND the measured end-to-end rate and p99 clear
  the bar — the line IS the BASELINE.json claim, artifact attached;
- ``BOTTLENECK <name> {json}`` otherwise — the named stage (or host
  defect) that caps the run, with every stage's measured time, its
  decisions/s in isolation, and the analytic per-subsystem HBM byte
  budget from ``step_ablation.hbm_bytes_model`` alongside, so the gap
  is attributed rather than hand-waved.

Stages:

- ``device_step``  — the fused grouped decide step chained under
  ``lax.scan`` (the pure device plane), slope-fitted across two scan
  lengths so per-dispatch overhead cancels; run per ``decide_impl`` the
  selector offers on this backend (``engine.decide.explain_decide_impl``;
  a megakernel it does not offer is recorded as skipped, with the reason
  the selector gives — nothing here runs under the interpreter).
- ``sharded_step`` — the same step through ``make_sharded_decide`` over
  every local device (the v5e-8 scaling arm; on a forced multi-device
  CPU host this measures dispatch overhead, not scaling, and says so).
- ``service``      — ``request_batch_arrays`` wall time through the
  token service (host prep + device + materialize), with per-dispatch
  p50/p99 — the latency evidence for the p99 < 2 ms clause.

``--smoke`` shrinks shapes so CI finishes in seconds; it still prints
the verdict line (CI greps for it) but writes no artifact. A full run
writes ``benchmarks/results/northstar-<ts>.json``; ``--publish rNN``
additionally pins ``benchmarks/results/NORTHSTAR_rNN.json`` — the
committed acceptance artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TARGET_DPS = 10_000_000
TARGET_P99_MS = 2.0
TARGET_FLOWS = 100_000
TARGET_CHIPS = 8


def _physical_cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - non-linux
        return os.cpu_count() or 1


def measure_device_step(config, impl: str, iters_lo: int, iters_hi: int,
                        reps: int, rng) -> dict:
    """Slope-fitted per-step time of the fused grouped+uniform decide
    chain for one ``decide_impl`` — the ``step_ablation`` methodology
    applied to the production step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sentinel_tpu.engine import (
        ClusterFlowRule, build_rule_table, make_batch, make_state,
    )
    from sentinel_tpu.engine.decide import _core_for
    from sentinel_tpu.engine.rules import ThresholdMode

    cfg = config._replace(decide_impl=impl)
    n_flows, N = cfg.max_flows, cfg.batch_size
    rules = [
        ClusterFlowRule(flow_id=i, count=100.0 + (i % 100),
                        mode=ThresholdMode.GLOBAL, namespace=f"ns{i % 16}")
        for i in range(n_flows)
    ]
    table, _ = build_rule_table(cfg, rules, ns_max_qps=1e9)
    K = 8
    batches = [
        make_batch(cfg, np.sort(rng.integers(0, n_flows, size=N)).tolist())
        for _ in range(K)
    ]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    core = _core_for(cfg, grouped=True)

    def timed(iters):
        def run(state, now0):
            ts = now0 + jnp.arange(iters, dtype=jnp.int32) * 7
            ks = jnp.arange(iters, dtype=jnp.int32) % K

            def body(st, xs):
                t, k = xs
                batch = jax.tree.map(lambda a: a[k], stacked)
                st, verdicts = core(
                    cfg, st, table, batch, t, grouped=True, uniform=True
                )
                return st, verdicts.status[0]

            return jax.lax.scan(body, state, (ts, ks))

        step = jax.jit(run)
        jax.block_until_ready(step(make_state(cfg), jnp.int32(10_000)))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(step(make_state(cfg), jnp.int32(10_000)))
            best = min(best, time.perf_counter() - t0)
        return best * 1e3

    t_lo, t_hi = timed(iters_lo), timed(iters_hi)
    step_ms = (t_hi - t_lo) / (iters_hi - iters_lo)
    if step_ms <= 0:  # fit failure on a noisy host: fall back to naive
        step_ms = t_hi / iters_hi
    return {
        "impl": impl,
        "step_ms": round(step_ms, 4),
        "decisions_per_sec": round(N / (step_ms / 1e3)),
    }


def measure_sharded_step(config, iters: int, reps: int, rng) -> dict:
    """One fused step through the flow-sharded mesh over every local
    device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sentinel_tpu.engine import (
        ClusterFlowRule, build_rule_table, make_batch, make_state,
    )
    from sentinel_tpu.engine.rules import ThresholdMode
    from sentinel_tpu.parallel.sharding import (
        make_flow_mesh, make_sharded_decide, shard_rules, shard_state,
    )

    n_dev = len(jax.devices())
    n_flows = config.max_flows - config.max_flows % n_dev
    cfg = config._replace(max_flows=max(n_dev, n_flows))
    N = cfg.batch_size
    rules = [
        ClusterFlowRule(flow_id=i, count=100.0 + (i % 100),
                        mode=ThresholdMode.GLOBAL, namespace=f"ns{i % 16}")
        for i in range(cfg.max_flows)
    ]
    table, _ = build_rule_table(cfg, rules, ns_max_qps=1e9)
    mesh = make_flow_mesh()
    state = shard_state(make_state(cfg), mesh)
    table = shard_rules(table, mesh)
    step = make_sharded_decide(cfg, mesh, grouped=True, uniform=True)
    K = 8
    batches = [
        make_batch(
            cfg, np.sort(rng.integers(0, cfg.max_flows, size=N)).tolist()
        )
        for _ in range(K)
    ]
    st = state
    jax.block_until_ready(step(st, table, batches[0], jnp.int32(10_000))[0])
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        st = state
        for i in range(iters):
            st, v = step(st, table, batches[i % K], jnp.int32(10_000 + 7 * i))
        jax.block_until_ready(v)
        best = min(best, time.perf_counter() - t0)
    step_ms = best * 1e3 / iters
    return {
        "devices": n_dev,
        "step_ms": round(step_ms, 4),
        "decisions_per_sec": round(N / (step_ms / 1e3)),
    }


def measure_service(config, n_dispatches: int, rng) -> dict:
    """``request_batch_arrays`` wall time through the token service —
    host prep + device step + verdict materialize, per dispatch."""
    import numpy as np

    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule
    from sentinel_tpu.engine.rules import ThresholdMode

    svc = DefaultTokenService(config)
    svc.load_rules(
        [
            ClusterFlowRule(flow_id=i, count=1e9, mode=ThresholdMode.GLOBAL)
            for i in range(min(config.max_flows, 4096))
        ],
        ns_max_qps=1e12,
    )
    svc.warmup()
    N = config.batch_size
    ids = np.sort(rng.integers(0, min(config.max_flows, 4096), size=N))
    ids = ids.astype(np.int64)
    times = []
    for _ in range(n_dispatches):
        t0 = time.perf_counter()
        svc.request_batch_arrays(ids)
        times.append((time.perf_counter() - t0) * 1e3)
    times = np.sort(np.asarray(times[2:]))  # drop warm-start dispatches
    p50 = float(times[int(0.50 * (len(times) - 1))])
    p99 = float(times[int(0.99 * (len(times) - 1))])
    return {
        "batch_size": N,
        "dispatches": n_dispatches,
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
        "decisions_per_sec": round(N / (p50 / 1e3)),
    }


def jax_backend() -> str:
    import jax

    return jax.default_backend()


def verdict(doc: dict) -> tuple:
    """(kind, name, summary): the acceptance decision and, when the bar
    is missed, WHICH stage (or host defect) is the limiter."""
    env = doc["env"]
    stages = doc["stages"]
    best_dps = max(
        (s["decisions_per_sec"] for s in stages["device_step"]
         if not s.get("skipped")),
        default=0,
    )
    shard = stages.get("sharded_step") or {}
    served = stages.get("service") or {}
    rate = max(best_dps, shard.get("decisions_per_sec", 0))
    p99 = served.get("p99_ms", float("inf"))
    if env["backend"] == "tpu" and env["devices"] >= TARGET_CHIPS:
        if rate >= TARGET_DPS and p99 < TARGET_P99_MS:
            return "BASELINE", "", (
                f"{rate / 1e6:.2f}M decisions/s across "
                f"{doc['n_flows']} flows at p99 {p99:.2f} ms on "
                f"{env['devices']}x {env['backend']}"
            )
        if rate < TARGET_DPS:
            return "BOTTLENECK", "device_step", (
                f"TPU mesh present but the kernel paces {rate / 1e6:.2f}M "
                f"decisions/s ({100 * rate / TARGET_DPS:.0f}% of target)"
            )
        return "BOTTLENECK", "service_p99", (
            f"rate clears ({rate / 1e6:.2f}M/s) but service p99 "
            f"{p99:.2f} ms >= {TARGET_P99_MS} ms"
        )
    if env["backend"] != "tpu":
        name = "host_no_tpu"
        why = (
            f"no TPU attached: {env['cores']}-core {env['backend']} host "
            f"paces {rate / 1e6:.2f}M decisions/s "
            f"({100 * rate / TARGET_DPS:.0f}% of the v5e-8 target)"
        )
        if env["cores"] < 4:
            name = "host_single_core"
            why = (
                f"{env['cores']}-core CPU host (shard-scaling demo needs "
                f">=4 physical cores, headline needs v5e-8): device plane "
                f"paces {rate / 1e6:.2f}M decisions/s "
                f"({100 * rate / TARGET_DPS:.0f}% of target), "
                f"service p99 {p99:.2f} ms"
            )
        return "BOTTLENECK", name, why
    return "BOTTLENECK", "mesh_too_small", (
        f"TPU backend but only {env['devices']} chip(s); the headline "
        f"needs {TARGET_CHIPS}"
    )


def run(smoke: bool = False, flows: int = TARGET_FLOWS,
        batch: int = 32768) -> dict:
    import jax
    import numpy as np

    from benchmarks.step_ablation import hbm_bytes_model
    from sentinel_tpu.core.compile_cache import ensure_compile_cache
    from sentinel_tpu.engine import EngineConfig
    from sentinel_tpu.engine.decide import explain_decide_impl

    ensure_compile_cache()

    if smoke:
        flows, batch = min(flows, 4096), min(batch, 1024)
        iters_lo, iters_hi, reps, n_disp = 8, 24, 2, 24
        shard_iters = 4
    else:
        iters_lo, iters_hi, reps, n_disp = 64, 256, 3, 200
        shard_iters = 32
    rng = np.random.default_rng(0)
    config = EngineConfig(
        max_flows=flows, max_namespaces=64, batch_size=batch
    )
    backend = jax_backend()
    doc = {
        "bench": "northstar",
        "target": {
            "decisions_per_sec": TARGET_DPS, "p99_ms": TARGET_P99_MS,
            "flows": TARGET_FLOWS, "chips": f"{TARGET_CHIPS}x v5e",
        },
        "env": {
            "backend": backend,
            "devices": len(jax.devices()),
            "cores": _physical_cores(),
            "smoke": smoke,
        },
        "n_flows": flows,
        "batch_size": batch,
        "stages": {},
        # the byte half of the budget: analytic per-subsystem HBM bytes
        # per step for both impls (see hbm_bytes_model's docstring)
        "hbm_budget": hbm_bytes_model(config, batch),
    }

    # stage 1: pure device step per impl the selector offers here. The
    # megakernel earns a cell only where "auto" could resolve to it;
    # elsewhere its row says why not, in the selector's words.
    auto_impl, auto_why = explain_decide_impl("auto")
    impls = ["xla"] + (["pallas"] if auto_impl == "pallas" else [])
    doc["stages"]["device_step"] = [
        measure_device_step(config, impl, iters_lo, iters_hi, reps, rng)
        for impl in impls
    ]
    if auto_impl != "pallas":
        doc["stages"]["device_step"].append({
            "impl": "pallas", "skipped": True, "why": auto_why,
        })

    # stage 2: the mesh arm
    try:
        doc["stages"]["sharded_step"] = measure_sharded_step(
            config, shard_iters, reps, rng
        )
        if backend != "tpu" and len(jax.devices()) > 1:
            doc["stages"]["sharded_step"]["note"] = (
                "forced host-device mesh: measures dispatch overhead, "
                "not chip scaling"
            )
    except Exception as e:  # pragma: no cover - degraded host
        doc["stages"]["sharded_step"] = {
            "error": f"{type(e).__name__}: {e}"[:160]
        }

    # stage 3: the service level (latency evidence)
    doc["stages"]["service"] = measure_service(
        config._replace(batch_size=min(batch, 4096)), n_disp, rng
    )

    kind, name, summary = verdict(doc)
    doc["verdict"] = {"kind": kind, "bottleneck": name, "summary": summary}
    return doc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI shapes; prints the verdict line, no artifact")
    ap.add_argument("--flows", type=int, default=TARGET_FLOWS)
    ap.add_argument("--batch", type=int, default=32768)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--publish", type=str, default="",
                    help="also pin results/NORTHSTAR_<rev>.json")
    args = ap.parse_args()
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    doc = run(smoke=args.smoke, flows=args.flows, batch=args.batch)
    line = json.dumps(doc)
    v = doc["verdict"]
    if v["kind"] == "BASELINE":
        print(f"BASELINE {json.dumps({'summary': v['summary']})}")
    else:
        print(f"BOTTLENECK {v['bottleneck']} "
              f"{json.dumps({'summary': v['summary']})}")
    print(line, flush=True)
    if args.smoke:
        return
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
    os.makedirs(d, exist_ok=True)
    ts = time.strftime("%Y%m%d-%H%M%S")
    with open(os.path.join(d, f"northstar-{ts}.json"), "w") as f:
        f.write(line + "\n")
    if args.publish:
        with open(os.path.join(
                d, f"NORTHSTAR_{args.publish}.json"), "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
