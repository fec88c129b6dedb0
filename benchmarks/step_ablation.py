"""Op-level ablation of the decide step: where does the per-step time go?

The roofline in ``bench.py`` shows the decide kernel is ~100× off both the
FLOP and HBM ceilings — the time is in serialized op chains, not math. This
bench times each candidate chain in isolation (chained under ``lax.scan``
exactly like the serving step, slope-decomposed across two scan lengths so
per-dispatch overhead cancels — see ``dispatch_decomp.py``):

- ``full``            — the production grouped+uniform step
- ``scatter4``        — the 4-channel window write path as shipped
- ``scatter4_sorted`` — same scatter with ``indices_are_sorted=True``
  (legal on the serving path: the batcher sorts the batch by flow slot,
  padding sorts after every real slot as out-of-range drop rows)
- ``scatter2``/``scatter1``/``scatter1_sorted`` — two/one channel(s)
  instead of four (channel-count scaling of the window write)
- ``gather``          — the windowed PASS read (2× window_sum_at + compare)
- ``nsguard_precise_arm`` — one-hot + blocked cumsum + einsum + dense
  column add: the guard's boundary-crossing arm, which production
  cond-gates (the ``full`` variant therefore times the guard fast path)
- ``prefix``          — the grouped segment-prefix (serving fast path)
- ``roll``            — the ring-bucket staleness reset alone

Prints ONE JSON line and records it under ``benchmarks/results/``.
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


def hbm_bytes_model(config, batch_size: int, pconfig=None) -> dict:
    """First-order analytic model of HBM bytes touched per decide step,
    split per subsystem — the byte half of the north-star per-stage budget
    (``northstar_bench.py`` embeds this next to the measured times).

    Accounting rules (stated so the numbers are auditable, not mystical):

    - HBM moves whole transactions, not cells: every access is charged
      ``max(bytes_requested, TXN)`` with ``TXN = 32`` (conservative —
      real TPU HBM bursts are larger, which only widens the gap);
    - a strided gather across a row (``window_sum_at`` pulls one channel
      column at stride ``E`` cells) touches every transaction the row
      spans, so it is charged the full ``[B, E]`` row;
    - scatter-add is an RMW — read transaction + write transaction per
      touched cell, even into a donated buffer;
    - roll is the conditional one-column staleness zero — charged
      separately as ``per_roll`` since its cadence is bucket-boundary
      crossings, not steps;
    - ``ops`` counts distinct HBM-touching accesses per batch row — the
      serialized scatter/gather chain length the roofline blames for the
      latency (each is its own dependency-ordered traversal in XLA; the
      megakernel folds them into one resident-in-VMEM pass).

    Two impls are modeled. ``xla`` is the shipped ``_decide_core``
    pipeline: each subsystem issues its own gathers and scatters, so a
    batch row's flow window is traversed once per subsystem op that
    touches it. ``pallas`` is the one-HBM-traversal megakernel
    (``ops/decide_pallas.py``): each referenced row's ``[B, E]`` flow
    window and ``[B, 1]`` occupy row are DMA'd into VMEM once, all
    subsystem math runs on the resident copy, and only the current
    bucket column of each written segment goes back — plus the XLA
    epilogue's [N]-sized scatters (shaping clocks, ns guard, verdict
    stitching), which stay outside the kernel by design.

    The ``sketch`` and ``outcome`` planes ride separate batches
    (PARAM_FLOW dispatches and OUTCOME_REPORT frames), so their rows are
    per *their* batch row, reported under ``off_step_planes``.
    """
    from sentinel_tpu.engine.state import (
        N_CLUSTER_EVENTS,
        N_OUTCOME_CHANNELS,
    )

    if pconfig is None:
        from sentinel_tpu.engine.param import ParamConfig

        pconfig = ParamConfig()
    N = batch_size
    F = config.max_flows
    B = config.n_buckets
    E = N_CLUSTER_EVENTS
    NS = config.max_namespaces
    C = 4  # bytes per cell
    TXN = 32  # HBM transaction granularity charged per access

    def t(requested):  # one access of `requested` contiguous bytes
        return max(int(requested), TXN)

    flow_row = B * E * C  # [B, E] bucket row, contiguous
    occ_row = B * 1 * C

    def sub(read, write, ops, per_roll=0):
        return {
            "read": int(read), "write": int(write),
            "total": int(read + write), "ops": int(ops),
            "per_roll": int(per_roll),
        }

    xla = {
        # PASS admission gather (strided column -> whole row) + 4 event
        # scatter-RMWs + the cond OCCUPIED_PASS channel; roll zeroes one
        # [F, E] column
        "windows": sub(
            read=N * (t(flow_row) + 5 * TXN),
            write=N * 5 * TXN,
            ops=1 + 5,
            per_roll=2 * F * E * C,
        ),
        # future-ring gather (expiring + matured share it) + add_future RMW
        "occupancy": sub(
            read=N * (t(occ_row) + TXN),
            write=N * TXN,
            ops=1 + 1,
            per_roll=2 * F * 1 * C,
        ),
        # 3 clock columns gathered at the batch rows, 3 scattered back (RMW)
        "shaping": sub(
            read=N * (3 * TXN + 3 * TXN), write=N * 3 * TXN, ops=3 + 3,
        ),
        # per-namespace qps window: gather at ns ids + dense column add
        "ns_guard": sub(
            read=N * t(occ_row) + t(NS * C),
            write=t(NS * C),
            ops=1 + 1,
        ),
    }
    # megakernel: one DMA in per referenced row (flow [B,E] + occupy
    # [B,1]; the 16 rule/shaping scalar columns stream in as contiguous
    # [N] VMEM blocks), one current-column DMA out per written segment
    # (<= N rows), then the epilogue's [N]-sized scatters
    pallas = {
        "windows": sub(
            read=N * t(flow_row) + 16 * N * C,
            write=N * t(E * C),
            ops=1 + 1,
            per_roll=2 * F * E * C,
        ),
        "occupancy": sub(
            read=N * t(occ_row),
            write=N * TXN,  # add_future RMW stays in the epilogue
            ops=1 + 1,
            per_roll=2 * F * 1 * C,
        ),
        # clock reads ride the 16-column block load; writes are epilogue
        # scatter-RMWs
        "shaping": sub(read=N * 3 * TXN, write=N * 3 * TXN, ops=3),
        "ns_guard": sub(  # epilogue, identical to the XLA arm
            read=N * t(occ_row) + t(NS * C),
            write=t(NS * C),
            ops=1 + 1,
        ),
    }
    for impl in (xla, pallas):
        impl["total"] = sub(
            read=sum(s["read"] for s in impl.values()),
            write=sum(s["write"] for s in impl.values()),
            ops=sum(s["ops"] for s in impl.values()),
            per_roll=sum(s["per_roll"] for s in impl.values()),
        )
    d, w = pconfig.depth, pconfig.width
    sd, sw = pconfig.slim_depth, pconfig.slim_width
    off_step = {
        # per PARAM_FLOW batch row: d hashed cells RMW (fat) + estimate
        # read + slim twin RMW when enabled
        "sketch": sub(
            read=N * (2 * d * TXN + (sd * TXN if pconfig.slim_enabled
                                     else 0)),
            write=N * (d * TXN + (sd * TXN if pconfig.slim_enabled
                                  else 0)),
            ops=2 * d + (2 * sd if pconfig.slim_enabled else 0),
            per_roll=2 * d * w * C + (2 * sd * sw * C
                                      if pconfig.slim_enabled else 0),
        ),
        # per OUTCOME_REPORT row: RT_SUM + COMPLETE + EXCEPTION + one
        # log2 histogram bucket, all scatter-RMW
        "outcome": sub(
            read=N * 4 * TXN, write=N * 4 * TXN, ops=4,
            per_roll=2 * F * N_OUTCOME_CHANNELS * C,
        ),
    }
    return {
        "batch_size": N,
        "cell_bytes": C,
        "txn_bytes": TXN,
        "per_step": {"xla": xla, "pallas": pallas},
        "per_decision": {
            "xla_bytes": round(xla["total"]["total"] / N, 2),
            "pallas_bytes": round(pallas["total"]["total"] / N, 2),
            "bytes_reduction": round(
                xla["total"]["total"] / max(1, pallas["total"]["total"]), 3
            ),
            "xla_hbm_ops": xla["total"]["ops"],
            "pallas_hbm_ops": pallas["total"]["ops"],
            "ops_reduction": round(
                xla["total"]["ops"] / max(1, pallas["total"]["ops"]), 3
            ),
        },
        "off_step_planes": off_step,
    }


def build_variants(config, table, stacked, n_flows):
    """Variant bodies with signature ``(state, (t, k)) -> (state, y)``.

    ``stacked`` holds K distinct pre-sorted batches stacked on a leading
    axis; each scan step gathers batch ``k`` — a VARYING batch per
    iteration, exactly like serving. With a loop-constant batch XLA hoists
    the batch-only chains (one-hot, prefix, masks) out of the scan and the
    ablation under-reports them (measured 40× on CPU)."""
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.engine.decide import _decide_core
    from sentinel_tpu.engine.prefix import segment_prefix_builder
    from sentinel_tpu.ops.scan_mm import blocked_cumsum
    from sentinel_tpu.stats import window as W

    spec = __import__(
        "sentinel_tpu.engine.state", fromlist=["flow_spec"]
    ).flow_spec(config)
    N = config.batch_size

    def pick(k):
        """Gather batch ``k`` from the stacked axis (per-iteration varying)."""
        return jax.tree.map(lambda a: a[k], stacked)

    def full(state, xs):
        t, k = xs
        state, verdicts = _decide_core(
            config, state, table, pick(k), t, grouped=True, uniform=True
        )
        return state, verdicts.status[0]

    def _scatter(state, t, k, channels, sorted_flag):
        b = pick(k)
        # the serving scatter layout: sorted real slots, padding pushed out
        # of range so mode="drop" discards it without breaking sortedness
        scatter_slot = jnp.where(
            b.valid, jnp.maximum(b.flow_slot, 0), n_flows
        )
        flow = W.roll(spec, state.flow, t)
        idx, _ = W.bucket_index(spec, t)
        counts = flow.counts
        for ch in range(channels):
            counts = counts.at[scatter_slot, idx, ch].add(
                b.acquire.astype(counts.dtype), mode="drop",
                indices_are_sorted=sorted_flag,
            )
        state = state._replace(flow=flow._replace(counts=counts))
        return state, counts[0, 0, 0]

    def scatter4(state, xs):
        return _scatter(state, xs[0], xs[1], 4, False)

    def scatter4_sorted(state, xs):
        return _scatter(state, xs[0], xs[1], 4, True)

    def scatter2(state, xs):
        return _scatter(state, xs[0], xs[1], 2, False)

    def scatter1(state, xs):
        return _scatter(state, xs[0], xs[1], 1, False)

    def scatter1_sorted(state, xs):
        return _scatter(state, xs[0], xs[1], 1, True)

    def gather(state, xs):
        t, k = xs
        b = pick(k)
        safe = jnp.maximum(b.flow_slot, 0)
        passed = (
            W.window_sum_at(spec, state.flow, t, 0, safe)
            + W.window_sum_at(spec, state.occupy, t, 0, safe)
        ).astype(jnp.float32)
        thr = table.count[safe]
        ok = (passed < thr).astype(jnp.float32)
        return state, jnp.sum(ok)

    def nsguard_precise_arm(state, xs):
        """The boundary-crossing arm of the namespace guard, run
        UNCONDITIONALLY: the production kernel cond-gates this chain on a
        namespace budget boundary falling inside the batch (rare), so the
        ``full`` variant above times the fast path; this variant is the
        guard's worst case."""
        t, k = xs
        b = pick(k)
        safe = jnp.maximum(b.flow_slot, 0)
        ns_id = table.namespace_id[safe]
        live_f = b.valid.astype(jnp.float32)
        ns_oh = (
            ns_id[:, None] == jnp.arange(config.max_namespaces)[None, :]
        ).astype(jnp.float32)
        ns_incl = blocked_cumsum(ns_oh * live_f[:, None])
        ns_prefix = (
            jnp.take_along_axis(ns_incl, ns_id[:, None], axis=1)[:, 0]
            - live_f
        )
        # gate on the windowed read so the chain is loop-carried like the
        # real guard (hoisting prevention is belt-and-braces: the varying
        # batch already defeats it)
        ns_already = W.window_sum_at(spec, state.ns, t, 0, ns_id)
        deltas = jnp.einsum(
            "nk,n->k", ns_oh,
            live_f * (ns_already + ns_prefix >= 0).astype(jnp.float32),
        )
        ns_ws = W.add_column(spec, state.ns, t, deltas)
        state = state._replace(ns=ns_ws)
        return state, jnp.sum(ns_prefix)

    def prefix(state, xs):
        t, k = xs
        b = pick(k)
        safe = jnp.maximum(b.flow_slot, 0)
        prefix_fn = segment_prefix_builder(safe, "grouped")
        contrib = b.valid.astype(jnp.float32)
        p = prefix_fn(contrib)
        # fold into carry via ns window so the scan can't DCE it
        ns_ws = W.add_column(spec, state.ns, t, jnp.zeros(
            (config.max_namespaces,), jnp.float32
        ).at[0].set(p[N - 1]))
        return state._replace(ns=ns_ws), p[0]

    def roll(state, xs):
        flow = W.roll(spec, state.flow, xs[0])
        return state._replace(flow=flow), flow.counts[0, 0, 0]

    return {
        "full": full,
        "scatter4": scatter4,
        "scatter4_sorted": scatter4_sorted,
        "scatter2": scatter2,
        "scatter1": scatter1,
        "scatter1_sorted": scatter1_sorted,
        "gather": gather,
        "nsguard_precise_arm": nsguard_precise_arm,
        "prefix": prefix,
        "roll": roll,
    }


def measure(batch_size: int = 32768, n_flows: int = 100_000,
            iters_lo: int = 64, iters_hi: int = 256, reps: int = 3,
            variants=None) -> dict:
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
    from sentinel_tpu.engine.rules import ThresholdMode

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    config = EngineConfig(
        max_flows=n_flows, max_namespaces=64, batch_size=batch_size
    )
    rules = [
        ClusterFlowRule(flow_id=i, count=100.0 + (i % 100),
                        mode=ThresholdMode.GLOBAL, namespace=f"ns{i % 64}")
        for i in range(n_flows)
    ]
    table, _ = build_rule_table(config, rules, ns_max_qps=1e9)
    K = 8  # distinct batches cycled through the scan
    batches = []
    for _ in range(K):
        slots = np.sort(rng.integers(0, n_flows, size=batch_size)).tolist()
        batches.append(make_batch(config, slots))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)

    bodies = build_variants(config, table, stacked, n_flows)
    if variants:
        bodies = {k: v for k, v in bodies.items() if k in variants}
    out = {
        "backend": dev.platform,
        "device": str(dev),
        "batch_size": batch_size,
        "n_flows": n_flows,
        "iters": [iters_lo, iters_hi],
        # analytic per-subsystem HBM budget next to the measured times —
        # northstar_bench.py lifts this into its per-stage budget
        "hbm_bytes": hbm_bytes_model(config, batch_size),
        "step_ms": {},
    }

    for name, body in bodies.items():
        def timed(iters):
            def run(state, now0):
                ts = now0 + jnp.arange(iters, dtype=jnp.int32)
                ks = jnp.arange(iters, dtype=jnp.int32) % K
                return jax.lax.scan(body, state, (ts, ks))

            step = jax.jit(run)
            o = step(make_state(config), jnp.int32(10_000))
            jax.block_until_ready(o)
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(
                    step(make_state(config), jnp.int32(10_000))
                )
                best = min(best, time.perf_counter() - t0)
            return best * 1e3

        try:
            t_lo = timed(iters_lo)
            t_hi = timed(iters_hi)
            d = (t_hi - t_lo) / (iters_hi - iters_lo)
            row = {"naive_ms_at_lo": round(t_lo / iters_lo, 4)}
            if d > 0:
                row["step_ms"] = round(d, 4)
            else:
                row["fit_failed"] = True
            out["step_ms"][name] = row
        except Exception as e:
            out["step_ms"][name] = f"error: {type(e).__name__}: {e}"[:160]
        print(json.dumps(out), flush=True)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32768)
    ap.add_argument("--flows", type=int, default=100_000)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--variants", type=str, default="")
    args = ap.parse_args()
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    doc = measure(
        batch_size=args.batch, n_flows=args.flows,
        variants=[v for v in args.variants.split(",") if v] or None,
    )
    line = json.dumps(doc)
    print(line, flush=True)
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(
            d, f"ablation-{time.strftime('%Y%m%d-%H%M%S')}.json"), "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
