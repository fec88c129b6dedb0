"""Benchmark: cluster token-server decision throughput on one chip.

Measures the steady-state device decision rate of the jitted token-verdict
kernel at the BASELINE.md configuration (100k flow rules), and prints ONE
JSON line.

Baseline: the reference token server's default per-namespace self-protection
cap of 30,000 decisions/s (``ServerFlowConfig.java:31``) — its own statement
of per-server scale (BASELINE.md). The north-star target is ≥10M/s across a
v5e-8, i.e. ≥1.25M/s per chip.

Structure:

- A parent that never imports jax (a process that has touched JAX holds the
  chip) starts ONE child, which does. There is no other rung: a child that
  does not find a TPU, or in which any stage fails, exits non-zero, and the
  parent passes that on without printing a result. Nothing here measures on
  the CPU and nothing carries an earlier run's number forward.
- The child STREAMS progressively-enriched JSON lines: the headline number
  prints the moment it is measured, then each enrichment stage (served rate,
  shape upgrade — adopted only if faster, roofline, per-bucket ladder, param
  pallas-vs-XLA, service latency percentiles, prefix-impl comparison)
  re-prints the full document; the parent prints the last one when the
  child has exited 0. Per-stage compile seconds are logged in ``extra``.
- The persistent compile cache is placed by
  ``sentinel_tpu.core.compile_cache.ensure_compile_cache``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

BASELINE_QPS = 30_000.0  # reference maxAllowedQps per namespace/server
METRIC = "flow_decisions_per_sec_per_chip_at_100k_rules"
REPO = os.path.dirname(os.path.abspath(__file__))

# The one measurement: 100k rules throughout (the metric is *at 100k
# rules*). budget_s < DEADLINE_S: the child skips stages it has no time
# left for and exits on its own inside the parent's deadline.
CHILD_CONFIG = dict(
    n_flows=100_000, batch=16384, chain=64, repeats=5, budget_s=2000,
    upgrade=[(32768, 32), (65536, 16), (131072, 8), (262144, 4)],
)
DEADLINE_S = 2400

# Single-chip peaks by ``device_kind``, for the roofline stage. A kind that
# is not here is an error, not a default. v5e: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM). The decide kernel forces f32
# matmuls (exact integer counts), so the honest MXU ceiling is ~1/4 of the
# bf16 peak.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


# ---------------------------------------------------------------------------
# Child: one process, streams enriched JSON documents
# ---------------------------------------------------------------------------


def _emit(doc: dict) -> None:
    sys.stdout.write(json.dumps(doc) + "\n")
    sys.stdout.flush()


def _measure(cfg: dict) -> None:
    t_child0 = time.perf_counter()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sentinel_tpu.core.compile_cache import ensure_compile_cache

    t_init0 = time.perf_counter()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"bench.py measures on a TPU; JAX found {devices}",
              file=sys.stderr)
        sys.exit(3)
    init_s = time.perf_counter() - t_init0
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

    n_flows = cfg["n_flows"]
    config = EngineConfig(
        max_flows=n_flows, max_namespaces=64, batch_size=cfg["batch"]
    )
    rules = [
        ClusterFlowRule(
            flow_id=i,
            count=100.0 + (i % 100),
            mode=ThresholdMode.GLOBAL,
            namespace=f"ns{i % 64}",
        )
        for i in range(n_flows)
    ]
    table, index = build_rule_table(config, rules, ns_max_qps=1e9)
    state = make_state(config)

    # The server pipelines micro-batches back-to-back, so the capacity
    # ceiling is the device's sustained batch rate — measured by scanning
    # a chain of batches inside ONE dispatch.
    chain = cfg["chain"]
    rng = np.random.default_rng(0)

    def timed_chained(econfig, etable, chain_n, repeats_n):
        """ONE measurement methodology for every shape: compile the
        chained-scan step for ``econfig``, warm up with a sanity read, then
        time ``repeats_n`` sustained dispatches. Both the headline and the
        shape-upgrade candidate ride this, so their rates are comparable
        by construction. The serving path the scan models: the host
        batcher groups same-flow requests (numpy stable sort, off the
        device critical path) and flags the uniform acquire=1 common case
        — decide() then takes its exact closed-form admission with no
        device sort (see token_service.request_batch)."""

        def chained(state, stacked_batches, now0):
            def body(carry, xs):
                st, nw = carry
                st, verdicts = _decide_core(
                    econfig, st, etable, xs, nw, grouped=True, uniform=True
                )
                return (st, nw + 1), verdicts.status

            (state, _), statuses = jax.lax.scan(
                body, (state, now0), stacked_batches
            )
            return state, statuses

        step = jax.jit(chained, donate_argnums=(0,))
        batches = []
        for _ in range(chain_n):
            slots = np.sort(
                rng.integers(0, n_flows, size=econfig.batch_size)
            ).tolist()
            batches.append(make_batch(econfig, slots))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
        nw = 10_000
        t_c0 = time.perf_counter()
        st, statuses = step(make_state(econfig), stacked, jnp.int32(nw))
        jax.block_until_ready(statuses)
        compile_s = time.perf_counter() - t_c0
        ok = float((np.asarray(statuses[0]) == TokenStatus.OK).mean())
        lat = []
        t_total0 = time.perf_counter()
        for _ in range(repeats_n):
            nw += chain_n
            t0 = time.perf_counter()
            st, statuses = step(st, stacked, jnp.int32(nw))
            jax.block_until_ready(statuses)
            lat.append(time.perf_counter() - t0)
        total = time.perf_counter() - t_total0
        return {
            "rate": repeats_n * chain_n * econfig.batch_size / total,
            "lat_ms": sorted(1e3 * x for x in lat),
            "ok_frac": ok,
            "compile_s": compile_s,
        }

    repeats = cfg["repeats"]
    m = timed_chained(config, table, chain, repeats)
    headline_compile_s = m["compile_s"]
    ok_frac = m["ok_frac"]
    assert ok_frac > 0.5, f"warmup sanity: ok fraction {ok_frac}"
    decisions_per_sec = m["rate"]
    lat_ms = m["lat_ms"]
    per_batch_med_ms = lat_ms[len(lat_ms) // 2] / chain
    now = 10_000 + repeats * chain

    doc = {
        "metric": METRIC,
        "value": round(decisions_per_sec),
        "unit": "decisions/s",
        "vs_baseline": round(decisions_per_sec / BASELINE_QPS, 2),
        "extra": {
            # honest stats: median/max wall time of a full chained
            # dispatch, and median device time per micro-batch.
            "dispatch_ms_p50": round(lat_ms[len(lat_ms) // 2], 2),
            "dispatch_ms_max": round(lat_ms[-1], 2),
            "per_batch_device_ms_med": round(per_batch_med_ms, 3),
            "batch_size": config.batch_size,
            "chain": chain,
            "n_flows": n_flows,
            "backend": dev.platform,
            "device": str(dev),
            "device_kind": dev.device_kind,
            "device_count": len(devices),
            "backend_init_s": round(init_s, 1),
            "compile_s": {"headline": round(headline_compile_s, 1)},
        },
    }
    _emit(doc)  # headline is now unlosable

    # ---- enrichment stages: each re-emits the full document when it lands.
    # A stage that raises ends the child non-zero: nothing is recorded and
    # carried on past.

    # per-stage floor: a stage started with less remaining wall budget than
    # this is skipped (and says so in the document) so the child exits on
    # its own inside the parent's deadline
    STAGE_FLOOR_S = 45.0

    def _budget_left():
        budget = cfg.get("budget_s")
        if budget is None:
            return float("inf")
        return budget - (time.perf_counter() - t_child0)

    def stage(name, fn):
        left = _budget_left()
        if left < STAGE_FLOOR_S:
            doc["extra"].setdefault("stage_skips", {})[name] = (
                f"skipped: {left:.0f}s of child budget left"
            )
            _emit(doc)
            return
        t0 = time.perf_counter()
        fn()
        doc["extra"]["compile_s"][name] = round(time.perf_counter() - t0, 1)
        _emit(doc)

    # roofline context (VERDICT r3 #5): analytic FLOPs/bytes per batch of
    # the uniform+grouped serving path, against this device kind's peaks.
    # Derivation in benchmarks/roofline.py (kept importable so the numbers
    # are auditable).
    def _roofline():
        from benchmarks.roofline import decide_step_model

        if dev.device_kind not in DEVICE_PEAKS:
            raise KeyError(
                f"no peaks recorded for device_kind {dev.device_kind!r}; "
                f"add it to DEVICE_PEAKS with its source "
                f"(known: {sorted(DEVICE_PEAKS)})"
            )
        peaks = DEVICE_PEAKS[dev.device_kind]
        f32_flops = peaks["bf16_flops"] / 4

        # read the shape from the doc, not the locals — the shape-upgrade
        # stage may have restated the headline for a larger batch
        model = decide_step_model(
            batch=doc["extra"]["batch_size"],
            n_namespaces=config.max_namespaces,
            n_buckets=config.n_buckets,
        )
        step_s = doc["extra"]["per_batch_device_ms_med"] / 1e3
        mfu_pct = model["flops"] / step_s / f32_flops * 100
        hbm_pct = model["bytes"] / step_s / peaks["hbm_bytes_per_s"] * 100
        doc["extra"]["roofline"] = {
            "device_kind": dev.device_kind,
            "flops_per_batch": model["flops"],
            "hbm_bytes_per_batch": model["bytes"],
            "mfu_pct_f32_peak": round(mfu_pct, 3),
            "mfu_pct_bf16_peak": round(mfu_pct / 4, 3),
            "hbm_bw_util_pct": round(hbm_pct, 2),
            "note": (
                "kernel is dispatch/latency-bound, not MXU- or HBM-bound "
                "— throughput headroom comes from larger batches; see "
                "benchmarks/roofline.py"
            ),
        }

    # shape upgrade: try a LARGER batch right after the headline — per-batch
    # step time grows sublinearly with batch on both measured backends (CPU
    # 4096→16384: 4× work, 2.4× time; TPU 1024→16384: 16× work, 2.2× time —
    # dispatch-bound, see roofline), so 2× batch projects 1.1–1.3×. The
    # headline only ever moves UP: a slower/failed candidate leaves it.
    def _shape_upgrade():
        upgrade = cfg.get("upgrade", (32768, 32))
        candidates = (
            list(upgrade) if isinstance(upgrade[0], (list, tuple))
            else [upgrade]
        )
        best = None
        tried = []
        for cand_batch, cand_chain in candidates:
            if cand_batch <= config.batch_size:
                continue
            # UNCONDITIONAL budget gate (a first candidate failing its
            # sanity check must not unleash an unguarded larger rung), and
            # size-aware: a ≥131072-batch compile costs more than the 45s
            # stage floor
            need_s = (3 if cand_batch <= 65536 else 6) * STAGE_FLOOR_S
            if _budget_left() < need_s:
                tried.append({
                    "batch": cand_batch, "chain": cand_chain,
                    "skipped": f"budget: {_budget_left():.0f}s left, "
                               f"need {need_s:.0f}s",
                })
                continue
            cfg_u = EngineConfig(
                max_flows=n_flows, max_namespaces=64, batch_size=cand_batch
            )
            table_u, _ = build_rule_table(cfg_u, rules, ns_max_qps=1e9)
            # same repeat count as the headline so adoption compares equal
            # sample sizes (r4 advisor)
            mu = timed_chained(cfg_u, table_u, cand_chain, repeats)
            tried.append({
                "batch": cand_batch, "chain": cand_chain,
                "decisions_per_sec": round(mu["rate"]),
                "ok_frac": round(mu["ok_frac"], 3),
            })
            if mu["ok_frac"] > 0.5 and (
                best is None or mu["rate"] > best[0]["rate"]
            ):
                best = (mu, cand_batch, cand_chain)
        measured = [t for t in tried if "decisions_per_sec" in t]
        if best is None:
            if tried:
                doc["extra"]["shape_upgrade"] = {
                    "tried": tried, "adopted": False,
                }
            return
        mu, cand_batch, cand_chain = best
        rate_u = mu["rate"]
        lat_u_ms = mu["lat_ms"]
        # same methodology AND same sanity gate as the headline (both come
        # from timed_chained), so adoption is apples-to-apples and a
        # degenerate table/shape can never publish a fast-but-meaningless
        # rate
        adopted = rate_u > doc["value"]
        doc["extra"]["shape_upgrade"] = {
            "batch": cand_batch, "chain": cand_chain,
            "decisions_per_sec": round(rate_u),
            "ok_frac": round(mu["ok_frac"], 3),
            "adopted": adopted,
            **({"tried": tried} if len(tried) > 1 or tried != measured
               else {}),
        }
        if adopted:
            # keep the pre-upgrade shape's stats coherent under their own
            # key, then restate every headline stat for the adopted shape
            doc["extra"]["pre_upgrade"] = {
                "decisions_per_sec": doc["value"],
                "batch_size": doc["extra"]["batch_size"],
                "chain": doc["extra"]["chain"],
                "dispatch_ms_p50": doc["extra"]["dispatch_ms_p50"],
                "dispatch_ms_max": doc["extra"]["dispatch_ms_max"],
                "per_batch_device_ms_med":
                    doc["extra"]["per_batch_device_ms_med"],
            }
            doc["value"] = round(rate_u)
            doc["vs_baseline"] = round(rate_u / BASELINE_QPS, 2)
            doc["extra"]["batch_size"] = cand_batch
            doc["extra"]["chain"] = cand_chain
            # median index, same as the headline's stats — index 1 of 5
            # sorted samples was the 40th percentile, understating p50 for
            # the adopted shape relative to pre_upgrade
            med = lat_u_ms[len(lat_u_ms) // 2]
            doc["extra"]["dispatch_ms_p50"] = round(med, 2)
            doc["extra"]["dispatch_ms_max"] = round(lat_u_ms[-1], 2)
            doc["extra"]["per_batch_device_ms_med"] = round(
                med / cand_chain, 3
            )

    # END-TO-END SERVED measurement on THIS backend (VERDICT r4 #1/#2): TCP
    # front door → micro-batcher → device kernel as one system. Closed-loop
    # served rate + RTT percentiles, then an open-loop load-latency curve
    # whose best SLO-meeting point is the "both halves of the north star at
    # one operating point" artifact. Runs FIRST among enrichment stages —
    # it is the round's top-priority evidence, and a long shape-upgrade
    # ladder must never drain the budget it needs.
    def _served():
        from benchmarks.serve_bench import serve_measure

        # the closed-loop fleet keeps tens of thousands of requests in
        # flight (4 clients × 4 pipelined threads × 4096/frame = 64k ≈ the
        # arena cap). The sweep starts low so the curve has unsaturated
        # points, not just the shed plateau. (A second candidate with
        # 16384-row frames used to ride along; a wire frame holds 5040
        # rows, so its clients died encoding and it measured 0 — on the
        # chip too, PR 21.)
        rates = (100_000, 250_000, 500_000, 1_000_000, 2_000_000)
        closed_kw = [dict(clients=4, batch=4096, pipeline=4, seconds=8.0)]
        sr = serve_measure(
            native=True, closed_kw=closed_kw, sweep_rates=rates,
            budget_s=min(_budget_left() - STAGE_FLOOR_S, 420.0),
        )
        doc["extra"]["served_rate"] = sr
        # hoist the frame-fusion evidence so the trajectory records the
        # dispatch-amortization win without digging into closed_loop
        fusion = (sr.get("closed_loop") or {}).get("fusion") or {}
        fd = fusion.get("fused_depth") or {}
        doc["extra"]["serve_fusion"] = {
            "fusion_depth": sr.get("fusion_depth"),
            "fused_frames_total": fusion.get("fused_frames_total"),
            "fused_depth_avg": fd.get("avg"),
            "fused_depth_max": fd.get("max"),
            "lane_occupancy": fusion.get("lane_occupancy"),
        }

    stage("served", _served)

    stage("shape_upgrade", _shape_upgrade)

    stage("roofline", _roofline)

    # per-serve-bucket device step time (the serving shape ladder the token
    # service actually dispatches). Each bucket is timed at TWO scan
    # lengths: measured(iters) = (overhead + iters·d)/iters, so the slope
    # between the two is the true per-step device time and the intercept is
    # the per-dispatch overhead (folding it into d once made a 64-batch
    # step look like ~1ms and pushed the projected p99 past the SLO).
    # Derivation: benchmarks/dispatch_decomp.py.
    def _buckets():
        per_bucket = {}
        dispatch_overhead = {}
        slopes = {}
        iters_lo, iters_hi = 100, 400
        for bucket in cfg.get("serve_buckets", (64, 1024, 4096, 16384)):
            if _budget_left() < STAGE_FLOOR_S:
                per_bucket[str(bucket)] = "skipped: child budget exhausted"
                continue
            cfgb = config._replace(batch_size=bucket)
            slots_b = np.sort(rng.integers(0, n_flows, size=bucket)).tolist()
            batch_b = jax.tree.map(jnp.asarray, make_batch(cfgb, slots_b))

            def timed_scan(iters):
                def chained_b(state, batch, now0):
                    def body(st, t):
                        st, verdicts = _decide_core(
                            cfgb, st, table, batch, t,
                            grouped=True, uniform=True,
                        )
                        # status head keeps the scan from being DCE'd
                        return st, verdicts.status[0]

                    ts = now0 + jnp.arange(iters, dtype=jnp.int32)
                    return jax.lax.scan(body, state, ts)

                step_b = jax.jit(chained_b)
                out = step_b(make_state(config), batch_b, jnp.int32(now))
                jax.block_until_ready(out)
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    jax.block_until_ready(
                        step_b(make_state(config), batch_b, jnp.int32(now))
                    )
                    best = min(best, time.perf_counter() - t0)
                return best * 1e3  # ms per whole dispatch

            t_lo = timed_scan(iters_lo)
            if _budget_left() < STAGE_FLOOR_S:
                # the hi-point jit is its own compile; never start it
                # without budget (same per-variant rule as the prefix stage)
                per_bucket[str(bucket)] = (
                    f"naive {t_lo / iters_lo:.4f} ms"
                    " (hi point skipped: budget)"
                )
                doc["extra"]["per_bucket_step_ms"] = per_bucket
                _emit(doc)
                continue
            t_hi = timed_scan(iters_hi)
            d_ms = (t_hi - t_lo) / (iters_hi - iters_lo)
            if d_ms <= 0:
                # jitter swamped the fit — publish the naive quotient,
                # clearly flagged, never a nonsense slope
                per_bucket[str(bucket)] = (
                    f"fit_failed: naive {t_lo / iters_lo:.4f} ms"
                )
                doc["extra"]["per_bucket_step_ms"] = per_bucket
                _emit(doc)
                continue
            slopes[str(bucket)] = d_ms  # unrounded, for the projection
            per_bucket[str(bucket)] = round(d_ms, 4)
            dispatch_overhead[str(bucket)] = round(t_lo - iters_lo * d_ms, 2)
            # progressive emit: a mid-compile kill keeps the rungs done
            doc["extra"]["per_bucket_step_ms"] = per_bucket
            doc["extra"]["per_bucket_dispatch_overhead_ms"] = (
                dispatch_overhead
            )
            _emit(doc)
        # projection from the measured device floors alone (the
        # served_rate stage measures the whole system): pipelined steps of
        # bucket B sustain B/d(B) with p99 ≈ 2·d(B) at pipelining depth 2
        # (one step queued behind the executing one). Clearly a projection,
        # clearly labeled.
        best = None
        for b_str, d_ms in slopes.items():  # unrounded, fit-ok rungs only
            proj = {
                "bucket": int(b_str),
                "decisions_per_sec": round(int(b_str) / d_ms * 1e3),
                "p99_ms_projected": round(2 * d_ms, 3),
            }
            if proj["p99_ms_projected"] < 2.0 and (
                best is None
                or proj["decisions_per_sec"] > best["decisions_per_sec"]
            ):
                best = proj
        doc["extra"]["colocated_projection"] = {
            "operating_point": best,
            "method": (
                "B/d(B) throughput, p99≈2·d(B), at pipelining depth 2; "
                "d(B) = slope of chained-scan wall time between scan "
                "lengths 100 and 400 (true per-step device time; the "
                "intercept — per-dispatch overhead — is reported "
                "separately in per_bucket_dispatch_overhead_ms)"
            ),
        }

    stage("per_bucket", _buckets)

    # segment-prefix implementation comparison at serving batch sizes
    # (VERDICT r3 #5: does the [N,N] matmul admission beat a segment scan?).
    # Times ONE prefix application per impl via a 100-iteration scan.
    def _prefix_compare():
        from sentinel_tpu.engine.prefix import segment_prefix_builder

        impls = ("matmul", "sort", "grouped", "pallas")
        res = {}
        for n in (256, 1024, 4096):
            keys = jnp.asarray(
                np.sort(rng.integers(0, n_flows, size=n)), jnp.int32
            )
            contrib = jnp.asarray(
                rng.random(n).astype(np.float32)
            )
            row = {}
            for impl in impls:
                # budget check per VARIANT, not just per stage: 12 compile
                # variants once overran the child budget
                if _budget_left() < STAGE_FLOOR_S:
                    row[impl] = "skipped: child budget exhausted"
                    continue
                prefix = segment_prefix_builder(keys, impl)

                def many(c):
                    def body(acc, _):
                        out = prefix(acc)
                        # feed output back (rescaled) so iterations chain
                        return out * 0.5 + c, out[0]

                    return jax.lax.scan(body, c, None, length=100)

                f = jax.jit(many)
                jax.block_until_ready(f(contrib))
                t0 = time.perf_counter()
                jax.block_until_ready(f(contrib))
                row[impl] = round(
                    (time.perf_counter() - t0) / 100 * 1e6, 1
                )
            res[str(n)] = row
            # progressive emit: a later kill keeps the sizes already done
            doc["extra"]["prefix_impl_us"] = res
            _emit(doc)


    # hot-param path: the CMS decide+update kernel, Pallas vs pure-XLA, both
    # compiled for this chip (VERDICT r3 #3).
    def _param():
        from sentinel_tpu.engine.param import (
            ParamConfig,
            hash_indices,
            make_param_state,
            param_decide,
        )

        res = {}
        N = 1024
        for impl in ("jax", "pallas"):
            if _budget_left() < STAGE_FLOOR_S:
                res[impl] = "skipped: child budget exhausted"
                continue
            pcfg = ParamConfig(max_param_rules=256, impl=impl)
            slots = jnp.asarray(
                rng.integers(0, 256, size=N).astype(np.int32)
            )
            idx = jnp.asarray(
                hash_indices(
                    rng.integers(0, 2**62, size=N), pcfg.depth, pcfg.width
                )
            )
            acq = jnp.ones((N,), jnp.int32)
            thr = jnp.full((N,), 1e9, jnp.float32)
            valid = jnp.ones((N,), bool)
            iters = 100

            def many(st, now0):
                def body(st, t):
                    st, admit, est = param_decide(
                        pcfg, st, slots, idx, acq, thr, valid, t
                    )
                    return st, admit[0]

                ts = now0 + jnp.arange(iters, dtype=jnp.int32)
                return jax.lax.scan(body, st, ts)

            f = jax.jit(many)
            st0 = make_param_state(pcfg)
            jax.block_until_ready(f(st0, jnp.int32(now)))
            t0 = time.perf_counter()
            jax.block_until_ready(f(st0, jnp.int32(now)))
            res[impl] = {
                "step_ms": round(
                    (time.perf_counter() - t0) / iters * 1e3, 4
                ),
                "impl": impl,
            }
        res["batch"] = N
        doc["extra"]["param_pallas_vs_xla_step_ms"] = res

    stage("param_pallas_vs_xla", _param)

    # service-level latency percentiles: wall time of
    # DefaultTokenService.request_batch_arrays per call (VERDICT r3 #2),
    # on record next to the device-step floor.
    def _latency():
        from sentinel_tpu.cluster.token_service import DefaultTokenService

        svc_cfg = EngineConfig(
            max_flows=4096, max_namespaces=64, batch_size=1024
        )
        service = DefaultTokenService(svc_cfg, serve_buckets=(64, 1024))
        service.load_rules(
            [
                ClusterFlowRule(
                    flow_id=i, count=1e6, mode=ThresholdMode.GLOBAL
                )
                for i in range(1024)
            ]
        )
        service.warmup()
        lat_doc = {}
        for bucket in (64, 1024):
            ids = rng.integers(0, 1024, size=bucket).astype(np.int64)
            for _ in range(5):
                service.request_batch_arrays(ids)
            reps = 200
            samples = np.empty(reps)
            for i in range(reps):
                t0 = time.perf_counter()
                service.request_batch_arrays(ids)
                samples[i] = time.perf_counter() - t0
            lat_doc[str(bucket)] = {
                "p50_ms": round(float(np.percentile(samples, 50)) * 1e3, 3),
                "p99_ms": round(float(np.percentile(samples, 99)) * 1e3, 3),
            }
        service.close()
        lat_doc["note"] = (
            "wall time per request_batch_arrays call on this host; "
            "per_bucket_step_ms is the device floor"
        )
        doc["extra"]["service_latency_ms"] = lat_doc

    stage("service_latency", _latency)

    # prefix-impl comparison is analysis, not a mandated artifact — it runs
    # LAST because its 9 compile variants are the most expensive stage
    stage("prefix_compare", _prefix_compare)


# ---------------------------------------------------------------------------
# Parent: starts the one child and reads its stream; never imports jax
# ---------------------------------------------------------------------------


def _run_child(cmd: list, deadline_s: float):
    """Run the child, keeping the LAST JSON line it printed; kill at the
    deadline. Returns ``(doc|None, returncode, stderr_tail)`` — returncode
    124 when the deadline killed it."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env,
    )
    last: list = [None]
    stderr_tail: list = []

    def _read_out():
        for line in proc.stdout:
            line = line.strip()
            if line.startswith("{"):
                try:
                    last[0] = json.loads(line)
                except json.JSONDecodeError:
                    pass

    def _read_err():
        for line in proc.stderr:
            stderr_tail.append(line.rstrip())
            del stderr_tail[:-20]

    to = threading.Thread(target=_read_out, daemon=True)
    te = threading.Thread(target=_read_err, daemon=True)
    to.start()
    te.start()
    try:
        rc = proc.wait(timeout=deadline_s)
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        rc = 124
    to.join(timeout=5)
    te.join(timeout=5)
    return last[0], rc, stderr_tail


def main() -> None:
    doc, rc, stderr_tail = _run_child(
        [sys.executable, os.path.abspath(__file__), "--run",
         json.dumps(CHILD_CONFIG)],
        DEADLINE_S,
    )
    if rc != 0 or doc is None:
        # no TPU, a failed stage, or the deadline: no result line, and the
        # child's own exit code (1 when it exited 0 without measuring)
        print("\n".join(stderr_tail), file=sys.stderr)
        print(f"bench.py: child exited {rc}; no measurement",
              file=sys.stderr)
        sys.exit(rc or 1)
    out = json.dumps(doc)
    print(out)
    _record(out)


def _record(line: str) -> None:
    """Commit-able copy of every bench emission (VERDICT round-1 #10)."""
    d = os.path.join(REPO, "benchmarks", "results")
    os.makedirs(d, exist_ok=True)
    stamp = time.strftime("%Y%m%d-%H%M%S")
    with open(os.path.join(d, f"bench-{stamp}.json"), "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--run":
        _measure(json.loads(sys.argv[2]))
    else:
        main()
