"""One-HBM-traversal decide megakernel.

The XLA pipeline (``engine/decide._decide_core``) walks the flow-window
plane once per subsystem: three windowed gathers for the admission read
(PASS + matured borrows + LEASED), a fourth row gather for the occupy
headroom check, the roll's full-``[F, E]`` stale-column multiply, and four
to five scatter-adds for the event writes — every one of them a separate
XLA op with its own HBM round trip over the same ``[F, B, E]`` rows. This
kernel fuses the whole per-flow traversal into ONE ``pallas_call`` over the
flow plane (the single-pass update discipline of the FPGA sketch pipeline,
arXiv:2504.16896):

- each batch row's ``[B, E]`` flow window and ``[B, 1]`` occupancy/future
  ring row is DMA'd into VMEM exactly once;
- the roll's stale-column zero becomes a *conditional* tiled DMA pass
  (the XLA path multiplies the column by 1 every step, stale or not);
- all admission math — warmup slope curve, windowed threshold read,
  grouped segment-prefix admission, pacing closed form, occupy headroom —
  runs on the VMEM-resident rows, sharing the exact helper functions of
  the XLA path (``_warmup_curve``, ``_occupy_feasible``,
  ``_grouped_prefix``) so the two backends are **bitwise** equal;
- the event deltas (PASS / PASS_REQUEST / BLOCK / BLOCK_REQUEST /
  OCCUPIED_PASS) are folded into per-segment totals and written back with
  one read-modify-write DMA per *flow segment* — the grouped-batch
  contract (same-flow rows contiguous) makes segment-tail writes race-free.

What stays outside the kernel, by design:

- The namespace guard window (``[NS, B, 1]`` — replicated, tiny) and every
  ``[N]``-sized scatter into the per-flow shaper-clock columns and the
  occupancy ring: those are O(batch) writes, not O(state) traversals, and
  the occupy write's ``pmax``-combined slot reset is a mesh collective,
  which cannot run inside a kernel. The kernel *reads* the occupancy ring
  rows (fused with the flow gather) and emits the charge vectors; the
  epilogue applies them through the same ``W.add_future`` call as the XLA
  path.
- The param sketch plane: it serves separate PARAM_FLOW batches and
  already has its own fused one-pass kernels (``cms_pallas``/
  ``salsa_pallas`` — the SALSA int16 packed-cell encoding lives there).

Parity discipline (the ``ops/cms_pallas.py`` twin contract):
``tests/test_ops_decide_pallas.py`` runs the kernel under the Pallas
interpreter on the CPU (its ``pallas_interpret`` fixture) and asserts
*bitwise* equality of verdicts and every state leaf against the XLA
pipeline over seeded mixed-behavior streams, including fused ``lax.scan``
depth and 8-virtual-device ``shard_map``. On the chip Mosaic refuses the
kernel today (``benchmarks/kernel_chip_parity.py`` tries the same
comparison compiled, and prints the compiler's message). All
cross-backend sums are integer-valued float32 (< 2^24), where addition
order cannot change the result; ``lax.cond``-gated XLA arms are replaced by unconditional
compute + select, which is bitwise-identical because the gated-off values
coincide (see ``_warmup_curve``'s docstring).

Backend selection: ``EngineConfig.decide_impl`` ("auto" is the XLA
pipeline everywhere, with the reason stated; ``SENTINEL_DECIDE_IMPL``
overrides) — see ``engine.decide.explain_decide_impl``. Nothing here picks
interpret mode: selected, the kernel is compiled by Mosaic or raises.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sentinel_tpu.engine.config import EngineConfig
from sentinel_tpu.engine.prefix import _grouped_prefix
from sentinel_tpu.engine.rules import RuleTable, ThresholdMode
from sentinel_tpu.engine.state import (
    ClusterEvent,
    EngineState,
    N_CLUSTER_EVENTS,
    ShapingState,
    flow_spec,
)
from sentinel_tpu.stats import window as W
from sentinel_tpu.stats.window import WindowState

# Per-request VMEM row buffers: [N, B, E] i32 must fit next to the scratch
# planes (1024 × 64 buckets × 6 events × 4B ≈ 1.5 MB at the deepest serve
# config). Steps with larger batches are built from the XLA core
# (``engine.decide.decide_core_name``).
MAX_BATCH = 1024

# stale-column zero pass: flow rows zeroed per DMA burst
_ZCHUNK = 512


def _make_decide_kernel(config: EngineConfig, F: int, N: int, uniform: bool):
    spec = flow_spec(config)
    B = spec.n_buckets
    E = N_CLUSTER_EVENTS
    bucket_ms = spec.bucket_ms
    interval_ms = spec.interval_ms
    refine_iters = config.admission_refine_iters
    ev = ClusterEvent
    # shared helpers — imported lazily to keep engine.decide's lazy import
    # of this module cycle-free
    from sentinel_tpu.engine.decide import _occupy_feasible, _warmup_curve

    def kernel(
        # inputs -----------------------------------------------------------
        flow_ref,  # ANY [F, B, E] i32 (aliased to flow_out_ref)
        occ_ref,  # ANY [F, B, 1] i32 (occupancy/future ring — read only)
        fstarts_ref,  # SMEM [B, 1] i32 — flow ring starts (pre-roll)
        ostarts_ref,  # SMEM [B, 1] i32 — occupy ring starts
        now_ref,  # SMEM [1, 1] i32
        slot_smem_ref,  # SMEM [N, 1] i32 — safe_slot (DMA loop scalars)
        wok_smem_ref,  # SMEM [N, 1] i32 — segment-tail & in-range write mask
        slot_ref,  # VMEM [N, 1] i32 — safe_slot
        acq_ref,  # VMEM [N, 1] i32
        live_ref,  # VMEM [N, 1] i32
        active_ref,  # VMEM [N, 1] i32 — ns-admitted & owned
        beh_ref,  # VMEM [N, 1] i32 — ControlBehavior
        prio_ref,  # VMEM [N, 1] i32
        factor_ref,  # VMEM [N, 1] f32 — AVG_LOCAL connected-count factor
        cnt_ref,  # VMEM [N, 1] f32 — rule count
        warn_ref,  # VMEM [N, 1] f32 — warmup warning line
        maxtok_ref,  # VMEM [N, 1] f32 — warmup bucket capacity
        slope_ref,  # VMEM [N, 1] f32
        cold_ref,  # VMEM [N, 1] f32
        maxq_ref,  # VMEM [N, 1] i32 — pacing queue bound
        lpt_ref,  # VMEM [N, 1] i32 — latestPassedTime rows
        wtok_ref,  # VMEM [N, 1] f32 — warmup stored tokens rows
        wfill_ref,  # VMEM [N, 1] i32 — warmup fill stamps rows
        # outputs ----------------------------------------------------------
        flow_out_ref,  # ANY [F, B, E] i32 (aliased)
        fstarts_out_ref,  # SMEM [B, 1] i32
        admit_ref,  # VMEM [N, 1] i32
        canocc_ref,  # VMEM [N, 1] i32
        paceacc_ref,  # VMEM [N, 1] i32
        pacewait_ref,  # VMEM [N, 1] i32
        passed_ref,  # VMEM [N, 1] f32
        thr_ref,  # VMEM [N, 1] f32
        admp_ref,  # VMEM [N, 1] f32 — admitted in-batch prefix
        wtoknew_ref,  # VMEM [N, 1] f32
        dosync_ref,  # VMEM [N, 1] i32
        lptsched_ref,  # VMEM [N, 1] i32 — now + round(l_rel)
        # scratch ----------------------------------------------------------
        fbuf,  # VMEM [N, B, E] i32 — gathered flow rows
        obuf,  # VMEM [N, B, 1] i32 — gathered occupy rows
        wcol,  # VMEM [N, 1, E] i32 — write-back columns
        zbuf,  # VMEM [_ZCHUNK, 1, E] i32 — zeros for the roll pass
        sem,  # DMA semaphore
    ):
        now = now_ref[0, 0]
        idx_cur = (now // bucket_ms) % B
        cur_start = now - now % bucket_ms

        # ---- roll bookkeeping: static unroll over the (tiny) ring --------
        stale = jnp.bool_(False)
        for b in range(B):
            is_cur = jnp.int32(b) == idx_cur
            stale = jnp.where(
                is_cur, fstarts_ref[b, 0] != cur_start, stale
            )
            fstarts_out_ref[b, 0] = jnp.where(
                is_cur, cur_start, fstarts_ref[b, 0]
            )
        fstarts_old = jnp.stack([fstarts_ref[b, 0] for b in range(B)])
        ostarts_old = jnp.stack([ostarts_ref[b, 0] for b in range(B)])

        # ---- conditional stale-column zero (the roll), tiled over F ------
        # Must run BEFORE the row gather: the gathered current-bucket cells
        # seed the read-modify-write totals below, and reads of the stale
        # column are masked out by the pre-roll validity mask either way.
        zbuf[...] = jnp.zeros((_ZCHUNK, 1, E), jnp.int32)

        @pl.when(stale)
        def _zero_stale_column():
            n_full = F // _ZCHUNK
            if n_full:

                def zb(k, carry):
                    dma = pltpu.make_async_copy(
                        zbuf,
                        flow_out_ref.at[
                            pl.ds(k * _ZCHUNK, _ZCHUNK), pl.ds(idx_cur, 1)
                        ],
                        sem,
                    )
                    dma.start()
                    dma.wait()
                    return carry

                jax.lax.fori_loop(0, n_full, zb, 0)
            rem = F % _ZCHUNK
            if rem:
                dma = pltpu.make_async_copy(
                    zbuf.at[pl.ds(0, rem)],
                    flow_out_ref.at[
                        pl.ds(n_full * _ZCHUNK, rem), pl.ds(idx_cur, 1)
                    ],
                    sem,
                )
                dma.start()
                dma.wait()

        # ---- the one traversal: DMA each request's flow + occupy row -----
        def gather(i, carry):
            row = slot_smem_ref[i, 0]
            d1 = pltpu.make_async_copy(
                flow_out_ref.at[pl.ds(row, 1)], fbuf.at[pl.ds(i, 1)], sem
            )
            d1.start()
            d1.wait()
            d2 = pltpu.make_async_copy(
                occ_ref.at[pl.ds(row, 1)], obuf.at[pl.ds(i, 1)], sem
            )
            d2.start()
            d2.wait()
            return carry

        jax.lax.fori_loop(0, N, gather, 0)

        fvals = fbuf[...]  # [N, B, E] i32
        ovals = obuf[...][:, :, 0]  # [N, B] i32

        slot = slot_ref[:, 0]
        acquire = acq_ref[:, 0]
        acquire_f = acquire.astype(jnp.float32)
        live = live_ref[:, 0] != 0
        active = active_ref[:, 0] != 0
        beh = beh_ref[:, 0]
        prio = prio_ref[:, 0] != 0
        factor = factor_ref[:, 0]
        cnt = cnt_ref[:, 0]

        # window validity masks from the PRE-roll starts, exactly like the
        # XLA path's W.window_sum_at / future_sum_at reads
        f_age = now - fstarts_old
        f_valid = ((f_age >= 0) & (f_age < interval_ms)).astype(jnp.int32)
        o_age = now - ostarts_old
        o_valid = ((o_age >= 0) & (o_age < interval_ms)).astype(jnp.int32)
        o_ahead = ostarts_old - now
        o_future = ((o_ahead > 0) & (o_ahead <= interval_ms)).astype(
            jnp.int32
        )

        pass_rows = fvals[:, :, int(ev.PASS)]  # [N, B]
        leased_rows = fvals[:, :, int(ev.LEASED)]
        # same int32 sum-then-cast chain as the XLA read path (exact)
        passed = (
            jnp.sum(pass_rows * f_valid[None, :], axis=1)
            + jnp.sum(ovals * o_valid[None, :], axis=1)
            + jnp.sum(leased_rows * f_valid[None, :], axis=1)
        ).astype(jnp.float32)

        # ---- traffic shaping masks + warmup curve (shared helper) --------
        is_warm = (beh == 1) | (beh == 3)
        is_pace = (beh == 2) | (beh == 3)
        warm_rows = active & is_warm
        pace_try = active & is_pace
        active_window = active & ~is_pace

        cnt_safe = jnp.maximum(cnt, 1e-6)
        qps, tokens_new, do_sync, _cur_sec = _warmup_curve(
            spec, now, passed, cnt, cnt_safe,
            warn_ref[:, 0], maxtok_ref[:, 0], slope_ref[:, 0],
            cold_ref[:, 0], wfill_ref[:, 0], wtok_ref[:, 0], warm_rows,
        )

        rate_qps = qps * factor * config.exceed_count
        threshold = rate_qps * (spec.interval_ms / 1000.0)

        # ---- grouped segment-prefix admission (same builder as XLA) ------
        flow_prefix = _grouped_prefix(slot)

        if uniform:
            a = jnp.max(jnp.where(live, acquire, 0)).astype(jnp.float32)
            a_safe = jnp.maximum(a, 1.0)
            rank = flow_prefix(active_window.astype(jnp.float32))
            admit = active_window & (passed + rank * a + a <= threshold)
            quota = jnp.floor(
                jnp.maximum(threshold - passed, 0.0) / a_safe
            )
            admitted_prefix = jnp.minimum(rank, quota) * a
        else:
            admit = active_window
            for _ in range(refine_iters):
                contrib = jnp.where(admit, acquire_f, 0.0)
                prefix = flow_prefix(contrib)
                admit = active_window & (
                    passed + prefix + acquire_f <= threshold
                )
            admitted_prefix = flow_prefix(
                jnp.where(admit, acquire_f, 0.0)
            )

        # ---- pacing closed form (see _decide_core §3b) -------------------
        # Computed unconditionally: with no RATE_LIMITER rows every mask is
        # False and the outputs coincide with the XLA path's cond-off arm.
        cost_f = jnp.round(
            1000.0 * acquire_f / jnp.maximum(rate_qps, 1e-6)
        )
        rel0 = jnp.maximum(
            lpt_ref[:, 0] - now, jnp.int32(-(2 ** 20))
        ).astype(jnp.float32)
        maxq = maxq_ref[:, 0].astype(jnp.float32)
        rev_prefix = _grouped_prefix(jnp.flip(slot))

        def pace_pass(accept):
            contrib = jnp.where(accept, cost_f, 0.0)
            incl = flow_prefix(contrib) + cost_f
            rank_p = flow_prefix(accept.astype(jnp.float32))
            first = accept & (rank_p == 0.0)
            # Segment-wide broadcast of the first accepted row's cost. The
            # XLA path scatters it through a [f_local] staging vector; in
            # the kernel the same value is the SEGMENT SUM of the
            # first-row-only costs (at most one nonzero per segment, and
            # adding zeros is exact in fp32) — prefix + own + suffix.
            t = jnp.where(first, cost_f, 0.0)
            c_first = (
                flow_prefix(t) + t + jnp.flip(rev_prefix(jnp.flip(t)))
            )
            l_rel = jnp.maximum(rel0, -c_first) + incl
            return l_rel

        accept = pace_try
        l_rel = pace_pass(accept)
        for _i in range(0 if uniform else refine_iters):
            accept = pace_try & (l_rel <= maxq)
            l_rel = pace_pass(accept)
        accept = pace_try & (l_rel <= maxq)
        wait_i = jnp.maximum(l_rel, 0.0).astype(jnp.int32)
        lpt_sched = now + jnp.round(l_rel).astype(jnp.int32)
        pace_now = accept & (wait_i == 0)
        pace_reject = pace_try & ~accept

        # ---- priority occupy headroom (shared helper; fused occupy read) -
        blocked = active_window & ~admit
        wait_next = bucket_ms - (now % bucket_ms)
        try_occupy = blocked & prio & (beh == 0)
        next_start = now + wait_next
        horizon = next_start - interval_ms
        exp_mask = (
            (f_valid != 0) & (fstarts_old <= horizon)
        ).astype(jnp.int32)
        expiring = jnp.sum(pass_rows * exp_mask[None, :], axis=1).astype(
            jnp.float32
        )
        waiting = jnp.sum(ovals * o_future[None, :], axis=1).astype(
            jnp.float32
        )
        occ_prefix = flow_prefix(jnp.where(try_occupy, acquire_f, 0.0))
        can_occupy = _occupy_feasible(
            config, try_occupy, passed, expiring, admitted_prefix,
            waiting, occ_prefix, acquire_f, threshold,
        )
        hard_block = blocked & ~can_occupy

        # ---- event deltas → per-segment totals → tail RMW write-back -----
        admit_i = (admit | pace_now).astype(jnp.int32)
        hard_i = (hard_block | pace_reject).astype(jnp.int32)
        deltas = [jnp.zeros((N,), jnp.int32)] * E
        deltas[int(ev.PASS)] = acquire * admit_i
        deltas[int(ev.PASS_REQUEST)] = admit_i
        deltas[int(ev.BLOCK)] = acquire * hard_i
        deltas[int(ev.BLOCK_REQUEST)] = hard_i
        # prioritized traffic's OCCUPIED_PASS mark: unconditional here —
        # with no prioritized rows the delta is zero, which is the XLA
        # path's cond-off arm
        deltas[int(ev.OCCUPIED_PASS)] = acquire * (
            admit & prio
        ).astype(jnp.int32)
        # inclusive segment totals via the same exact-f32 grouped prefix;
        # the segment-tail row carries the whole segment's delta
        totals = [
            (flow_prefix(d.astype(jnp.float32)) + d.astype(jnp.float32))
            .astype(jnp.int32)
            for d in deltas
        ]
        cur_col = jax.lax.dynamic_slice_in_dim(fvals, idx_cur, 1, axis=1)[
            :, 0, :
        ]  # [N, E] — post-roll values (stale column was zeroed pre-gather)
        new_col = cur_col + jnp.stack(totals, axis=1)
        wcol[...] = new_col[:, None, :]

        def write_back(i, carry):
            @pl.when(wok_smem_ref[i, 0] != 0)
            def _():
                row = slot_smem_ref[i, 0]
                dma = pltpu.make_async_copy(
                    wcol.at[pl.ds(i, 1)],
                    flow_out_ref.at[pl.ds(row, 1), pl.ds(idx_cur, 1)],
                    sem,
                )
                dma.start()
                dma.wait()

            return carry

        jax.lax.fori_loop(0, N, write_back, 0)

        # ---- [N] decision outputs for the epilogue -----------------------
        admit_ref[:, 0] = admit.astype(jnp.int32)
        canocc_ref[:, 0] = can_occupy.astype(jnp.int32)
        paceacc_ref[:, 0] = accept.astype(jnp.int32)
        pacewait_ref[:, 0] = wait_i
        passed_ref[:, 0] = passed
        thr_ref[:, 0] = threshold
        admp_ref[:, 0] = admitted_prefix
        wtoknew_ref[:, 0] = tokens_new
        dosync_ref[:, 0] = do_sync.astype(jnp.int32)
        lptsched_ref[:, 0] = lpt_sched

    return kernel


def _call_decide_kernel(
    config: EngineConfig,
    flow_counts: jax.Array,  # [F, B, E] i32
    occ_counts: jax.Array,  # [F, B, 1] i32
    fstarts: jax.Array,  # [B] i32
    ostarts: jax.Array,  # [B] i32
    now: jax.Array,
    safe_slot: jax.Array,  # [N] i32
    write_ok: jax.Array,  # [N] bool — segment tail & in-range
    acquire: jax.Array,
    live: jax.Array,
    active: jax.Array,
    beh: jax.Array,
    prioritized: jax.Array,
    factor: jax.Array,
    cnt: jax.Array,
    warn: jax.Array,
    max_token: jax.Array,
    slope: jax.Array,
    cold_count: jax.Array,
    max_queue_ms: jax.Array,
    lpt_rows: jax.Array,
    wtok_rows: jax.Array,
    wfill_rows: jax.Array,
    uniform: bool,
):
    F, B, E = flow_counts.shape
    N = safe_slot.shape[0]
    kernel = _make_decide_kernel(config, F, N, uniform)

    def col_i32(x):
        return x.astype(jnp.int32).reshape(N, 1)

    def col_f32(x):
        return x.astype(jnp.float32).reshape(N, 1)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    outs = pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            smem, smem, smem, smem, smem,
        ] + [vmem] * 16,
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            smem,
        ) + (vmem,) * 10,
        out_shape=(
            jax.ShapeDtypeStruct((F, B, E), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.int32),  # admit
            jax.ShapeDtypeStruct((N, 1), jnp.int32),  # can_occupy
            jax.ShapeDtypeStruct((N, 1), jnp.int32),  # pace accept
            jax.ShapeDtypeStruct((N, 1), jnp.int32),  # pace wait
            jax.ShapeDtypeStruct((N, 1), jnp.float32),  # passed
            jax.ShapeDtypeStruct((N, 1), jnp.float32),  # threshold
            jax.ShapeDtypeStruct((N, 1), jnp.float32),  # admitted prefix
            jax.ShapeDtypeStruct((N, 1), jnp.float32),  # warm tokens'
            jax.ShapeDtypeStruct((N, 1), jnp.int32),  # warm do_sync
            jax.ShapeDtypeStruct((N, 1), jnp.int32),  # lpt schedule
        ),
        input_output_aliases={0: 0},
        scratch_shapes=[
            pltpu.VMEM((N, B, E), jnp.int32),
            pltpu.VMEM((N, B, 1), jnp.int32),
            pltpu.VMEM((N, 1, E), jnp.int32),
            pltpu.VMEM((_ZCHUNK, 1, E), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
        cost_estimate=pl.CostEstimate(
            # per step: N row gathers (flow + occupy) + N tail writes +
            # the amortized stale-column zero; flops dominated by the
            # [N]-vector admission math and the grouped prefixes
            flops=20 * N * B * E,
            bytes_accessed=4 * (2 * N * B * (E + 1) + N * E + F * E // B),
            transcendentals=0,
        ),
    )(
        flow_counts,
        occ_counts,
        fstarts.reshape(B, 1).astype(jnp.int32),
        ostarts.reshape(B, 1).astype(jnp.int32),
        jnp.asarray(now, jnp.int32).reshape(1, 1),
        col_i32(safe_slot),
        col_i32(write_ok),
        col_i32(safe_slot),
        col_i32(acquire),
        col_i32(live),
        col_i32(active),
        col_i32(beh),
        col_i32(prioritized),
        col_f32(factor),
        col_f32(cnt),
        col_f32(warn),
        col_f32(max_token),
        col_f32(slope),
        col_f32(cold_count),
        col_i32(max_queue_ms),
        col_i32(lpt_rows),
        col_f32(wtok_rows),
        col_i32(wfill_rows),
    )
    return outs


def decide_core_pallas(
    config: EngineConfig,
    state: EngineState,
    rules: RuleTable,
    batch,
    now: jax.Array,
    axis_name: Optional[str] = None,
    grouped: bool = False,
    uniform: bool = False,
) -> tuple:
    """Drop-in ``_decide_core`` twin backed by the megakernel.

    Same signature, same pytree outputs, bitwise-equal results. Requires
    the grouped-batch contract and a batch within ``MAX_BATCH``; the choice
    between this core and the XLA one is made where it can be reported,
    in ``engine.decide.decide_core_name``.
    """
    # lazy (mutual recursion with engine.decide's backend dispatch), and via
    # importlib because the package re-exports a `decide` FUNCTION that
    # shadows the module attribute
    import importlib

    D = importlib.import_module("sentinel_tpu.engine.decide")

    N = batch.valid.shape[0]
    if not grouped or N > MAX_BATCH:
        raise ValueError(
            f"decide_core_pallas needs a grouped batch of at most "
            f"{MAX_BATCH} rows (got grouped={grouped}, {N} rows); "
            "engine.decide._core_for picks the core"
        )

    spec = flow_spec(config)
    now = jnp.asarray(now, jnp.int32)
    f_local = rules.valid.shape[0]

    if axis_name is not None:
        offset = jax.lax.axis_index(axis_name).astype(jnp.int32) * f_local
        psum = partial(jax.lax.psum, axis_name=axis_name)
        pmax = partial(jax.lax.pmax, axis_name=axis_name)
    else:
        offset = jnp.int32(0)
        psum = lambda x: x  # noqa: E731
        pmax = lambda x: x  # noqa: E731

    # ---- prologue: identical [N]-sized setup + namespace guard ----------
    local_slot = batch.flow_slot - offset
    in_range = (
        (batch.flow_slot >= 0) & (local_slot >= 0) & (local_slot < f_local)
    )
    safe_slot = jnp.where(in_range, local_slot, 0)
    owned = in_range & rules.valid[safe_slot]
    has_rule = psum(owned.astype(jnp.int32)) > 0
    live = batch.valid & has_rule
    no_rule = batch.valid & ~has_rule
    acquire_f = batch.acquire.astype(jnp.float32)

    ns_id, ns_ok, seg_ns_sum = D._ns_guard(
        config, spec, state.ns, rules, now, psum, owned, safe_slot, live
    )
    too_many = live & ~ns_ok
    ns_admitted = live & ns_ok
    active = ns_admitted & owned

    # circuit breakers run in the prologue with the SAME shared gate (and
    # the same grouped prefix builder) as the XLA path, and degraded rows
    # are stripped from `active` BEFORE the kernel sees it — the megakernel
    # then treats them exactly like inactive rows (zero event deltas, no
    # admission), so kernel parity holds by construction with zero kernel
    # changes
    degraded, br_retry, breaker_ws = D._breaker_gate(
        config, spec, state, rules, now, safe_slot, active,
        _grouped_prefix(safe_slot), psum,
    )
    active = active & ~degraded

    conn = rules.ns_connected[ns_id].astype(jnp.float32)
    factor = jnp.where(
        rules.mode[safe_slot] == int(ThresholdMode.AVG_LOCAL), conn, 1.0
    )
    beh = rules.behavior[safe_slot].astype(jnp.int32)
    is_pace = (beh == 2) | (beh == 3)
    pace_try_mask = active & is_pace
    active_window = active & ~is_pace

    # One write-back row per safe_slot segment: the LAST in-range row. The
    # grouped contract makes equal flow slots contiguous, but foreign-shard
    # and padding rows all collapse onto safe_slot 0 and can merge with an
    # owned slot-``offset`` segment on either side; their deltas are
    # provably zero (active ⊆ owned ⊆ in_range), so the last in-range row's
    # inclusive segment total already carries the whole segment — and
    # skipping the non-in-range tail keeps the slot-0 RMW from clobbering a
    # real segment's update. In-range rows of one segment share one
    # flow_slot, hence are contiguous: exactly one writer per physical row.
    next_same = jnp.concatenate(
        [safe_slot[1:] == safe_slot[:-1], jnp.zeros((1,), bool)]
    )
    next_in = jnp.concatenate([in_range[1:], jnp.zeros((1,), bool)])
    write_ok = in_range & ~(next_same & next_in)

    (
        flow_counts_out, fstarts_out,
        admit_o, canocc_o, paceacc_o, pacewait_o,
        passed_o, thr_o, admp_o, wtoknew_o, dosync_o, lpts_o,
    ) = _call_decide_kernel(
        config,
        state.flow.counts,
        state.occupy.counts,
        state.flow.starts,
        state.occupy.starts,
        now,
        safe_slot,
        write_ok,
        batch.acquire,
        live,
        active,
        beh,
        batch.prioritized,
        factor,
        rules.count[safe_slot],
        rules.warning_token[safe_slot],
        rules.max_token[safe_slot],
        rules.slope[safe_slot],
        rules.cold_count[safe_slot],
        rules.max_queue_ms[safe_slot],
        state.shaping.lpt[safe_slot],
        state.shaping.warm_tokens[safe_slot],
        state.shaping.warm_filled[safe_slot],
        uniform,
    )

    admit = admit_o[:, 0] != 0
    can_occupy = canocc_o[:, 0] != 0
    pace_admit = paceacc_o[:, 0] != 0
    pace_wait = pacewait_o[:, 0]
    passed = passed_o[:, 0]
    threshold = thr_o[:, 0]
    admitted_prefix = admp_o[:, 0]
    tokens_new = wtoknew_o[:, 0]
    do_sync = dosync_o[:, 0] != 0
    lpt_sched = lpts_o[:, 0]

    pace_now = pace_admit & (pace_wait == 0)
    pace_later = pace_admit & (pace_wait > 0)
    pace_reject = pace_try_mask & ~pace_admit
    hard_block = (active_window & ~admit) & ~can_occupy
    wait_next = spec.bucket_ms - (now % spec.bucket_ms)

    flow_ws = WindowState(starts=fstarts_out[:, 0], counts=flow_counts_out)

    # ---- epilogue: O(batch) scatters + collectives, same as the XLA path
    cur_sec = now - now % 1000
    scat_w = jnp.where(do_sync, safe_slot, f_local)
    warm_tokens_ws = state.shaping.warm_tokens.at[scat_w].set(
        tokens_new, mode="drop"
    )
    warm_filled_ws = state.shaping.warm_filled.at[scat_w].set(
        cur_sec, mode="drop"
    )
    scat_l = jnp.where(pace_admit, safe_slot, f_local)
    lpt_ws = state.shaping.lpt.at[scat_l].max(lpt_sched, mode="drop")

    any_prio = jnp.any(batch.prioritized & batch.valid)
    any_pace = jnp.any(psum(pace_try_mask.astype(jnp.int32)) > 0)
    charge_wait = jnp.where(
        can_occupy, jnp.full((N,), wait_next, jnp.int32), pace_wait
    )
    charge_valid = can_occupy | pace_later
    occupy_ws = jax.lax.cond(
        any_prio | any_pace,
        lambda occ: W.add_future(
            spec, occ, now,
            wait_ms=charge_wait,
            resource_ids=safe_slot,
            channel_ids=jnp.zeros((N,), jnp.int32),
            values=batch.acquire,
            valid=charge_valid,
            combine_desired=pmax,
        ),
        lambda occ: occ,
        state.occupy,
    )
    ns_deltas = seg_ns_sum(ns_admitted.astype(jnp.float32))
    ns_ws = W.add_column(spec, state.ns, now, ns_deltas)

    # ---- verdict stitching (identical to _decide_core §6) ---------------
    TokenStatus = D.TokenStatus
    local_status = jnp.where(
        degraded,
        int(TokenStatus.DEGRADED) + 1,
        jnp.where(
            admit | pace_now,
            int(TokenStatus.OK) + 1,
            jnp.where(
                can_occupy | pace_later,
                int(TokenStatus.SHOULD_WAIT) + 1,
                jnp.where(
                    hard_block | pace_reject,
                    int(TokenStatus.BLOCKED) + 1, 0
                ),
            ),
        ),
    ).astype(jnp.int32)
    combined = psum(local_status)
    status = jnp.where(
        ~batch.valid,
        int(TokenStatus.FAIL),
        jnp.where(
            no_rule,
            int(TokenStatus.NO_RULE_EXISTS),
            jnp.where(
                too_many,
                int(TokenStatus.TOO_MANY_REQUEST),
                jnp.where(
                    combined > 0, combined - 1, int(TokenStatus.FAIL)
                ),
            ),
        ),
    ).astype(jnp.int8)
    wait_ms = psum(
        jnp.where(
            can_occupy, wait_next, jnp.where(pace_later, pace_wait, 0)
        ).astype(jnp.int32)
    )
    remaining_local = jnp.clip(
        threshold - passed - admitted_prefix
        - jnp.where(admit, acquire_f, 0.0),
        0.0,
        2 ** 30,
    ).astype(jnp.int32)
    remaining = psum(
        jnp.where(admit, remaining_local, jnp.where(degraded, br_retry, 0))
    )

    new_state = EngineState(
        flow=flow_ws, occupy=occupy_ws, ns=ns_ws,
        shaping=ShapingState(
            lpt=lpt_ws, warm_tokens=warm_tokens_ws,
            warm_filled=warm_filled_ws,
        ),
        outcome=state.outcome,
        breaker=breaker_ws,
    )
    verdicts = D.VerdictBatch(
        status=status, wait_ms=wait_ms, remaining=remaining
    )
    return new_state, verdicts
