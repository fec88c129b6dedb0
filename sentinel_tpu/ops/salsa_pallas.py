"""SALSA decide+update as a single Pallas TPU kernel.

Semantics match ``sketch.salsa.salsa_decide_jax`` — the windowed-CMS decide
of ``ops/cms_pallas.py`` over the SALSA int16 pair encoding
(``sketch/salsa.py``): planes live in HBM as ``[B*D, P, C]`` int16 with
``C = 2*width`` cells, each plane is DMA'd into VMEM on demand, and all
gathers/scatters are the same one-hot MXU matmuls as the cms kernel, just
over a decoded int32 view of the plane.

Pair arithmetic avoids minor-dimension strided slices (which Mosaic may
refuse) by operating on full-width lane vectors: a cell's pair partner is a
parity-selected ``jnp.roll`` by ±1 lane, and even/odd masks come from a
lane iota. The decode/encode is therefore pure elementwise + roll. A kernel
Mosaic refuses loses the ``impl="auto"`` probe out loud
(``engine.param.explain_param_impl`` logs and returns the compiler's
message); forced with ``impl="pallas"`` it raises.

Estimates travel through f32 accumulators exactly like the cms kernel, so
they are exact below 2^24 — far above any admissible window threshold, and
the parity suite pins the no-undercount behavior for both impls.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sentinel_tpu.ops.cms_pallas import refuse_past_vmem
from sentinel_tpu.sketch.salsa import CAP, MERGE_CEIL, SAT

MAX_BATCH = 1024
# same VMEM request and matmul precision as ops/cms_pallas.py, for the same
# reasons (the [N, C] one-hots are twice as wide here)
_VMEM_LIMIT = 64 * 1024 * 1024
_EXACT = jax.lax.Precision.HIGHEST


def _make_kernel(P: int, B: int, D: int, C: int, bucket_ms: int,
                 refine_iters: int):
    interval_ms = bucket_ms * B

    def _pairs(x32):
        """Elementwise pair views of a ``[P, C]`` int32 plane:
        ``(lo, hi, merged)`` per CELL (both lanes of a pair agree)."""
        even = (
            jax.lax.broadcasted_iota(jnp.int32, (P, C), 1) % 2 == 0
        )
        partner = jnp.where(
            even, jnp.roll(x32, -1, axis=1), jnp.roll(x32, 1, axis=1)
        )
        lo = jnp.where(even, x32, partner)
        hi = jnp.where(even, partner, x32)
        return even, lo, hi, hi < 0

    def _qdecode(x16):
        """Query view [P, C] f32: both cells of a merged pair read the
        merged value."""
        x32 = x16.astype(jnp.int32)
        _even, lo, hi, merged = _pairs(x32)
        mval = lo + CAP * (-hi - 1)
        return jnp.where(merged, mval, x32).astype(jnp.float32), merged

    def kernel(
        counts_ref,  # ANY [B*D, P, C] int16 (aliased to counts_out_ref)
        starts_ref,  # SMEM [B, 1] int32
        now_ref,  # SMEM [1, 1] int32
        slot_ref,  # VMEM [N, 1] int32
        idx_ref,  # VMEM [N, D] int32
        acq_ref,  # VMEM [N, 1] int32
        thr_ref,  # VMEM [N, 1] float32
        valid_ref,  # VMEM [N, 1] int32
        counts_out_ref,  # ANY [B*D, P, C] int16
        starts_out_ref,  # SMEM [B, 1] int32
        admit_ref,  # VMEM [N, 1] int32
        est_ref,  # VMEM [N, 1] int32
        merges_ref,  # VMEM [P, 1] int32 (newly merged pairs this step)
        plane_buf,  # VMEM scratch [1, P, C] int16
        sem,  # DMA semaphore
    ):
        N = slot_ref.shape[0]
        now = now_ref[0, 0]
        cur_b = (now // bucket_ms) % B
        cur_start = now - now % bucket_ms

        stale = jnp.bool_(False)
        for b in range(B):
            is_cur = jnp.int32(b) == cur_b
            stale = jnp.where(is_cur, starts_ref[b, 0] != cur_start, stale)
            starts_out_ref[b, 0] = jnp.where(
                is_cur, cur_start, starts_ref[b, 0]
            )

        slot = slot_ref[:, 0]
        live = (valid_ref[:, 0] != 0) & (slot >= 0)
        safe_slot = jnp.where(slot >= 0, slot, 0)
        oh_slot = (
            safe_slot[:, None]
            == jax.lax.broadcasted_iota(jnp.int32, (N, P), 1)
        ).astype(jnp.float32)
        oh_idx = [
            (
                idx_ref[:, d][:, None]
                == jax.lax.broadcasted_iota(jnp.int32, (N, C), 1)
            ).astype(jnp.float32)
            for d in range(D)
        ]
        acq = acq_ref[:, 0].astype(jnp.float32)

        # ---- estimate: min over depth of windowed decoded-cell sums ----
        est = None
        for d in range(D):
            acc = jnp.zeros((N,), jnp.float32)
            for b in range(B):
                start_b = starts_out_ref[b, 0]
                age = now - start_b
                ok = (age >= 0) & (age < interval_ms)
                ok = ok & ~(stale & (jnp.int32(b) == cur_b))
                dma = pltpu.make_async_copy(
                    counts_ref.at[pl.ds(b * D + d, 1)], plane_buf, sem
                )
                dma.start()
                dma.wait()
                qdec, _m = _qdecode(plane_buf[0])
                rows = jnp.dot(
                    oh_slot, qdec, preferred_element_type=jnp.float32,
                    precision=_EXACT,
                )  # [N, C]
                cell = jnp.sum(rows * oh_idx[d], axis=1)
                acc = acc + jnp.where(ok, cell, 0.0)
            est = acc if est is None else jnp.minimum(est, acc)

        # ---- in-batch prefix admission (same as the cms kernel) ----
        key = safe_slot
        for d in range(D):
            key = key * jnp.int32(-1640531527) + idx_ref[:, d]
        row_i = jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
        col_i = jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)
        mask = ((key[:, None] == key[None, :]) & (row_i > col_i)).astype(
            jnp.float32
        )
        thr = thr_ref[:, 0]
        admit = live
        for _ in range(refine_iters):
            contrib = jnp.where(admit, acq, 0.0)
            prefix = jnp.dot(
                mask, contrib[:, None], preferred_element_type=jnp.float32,
                precision=_EXACT,
            )[:, 0]
            admit = live & (est + prefix + acq <= thr)

        # ---- update current-bucket planes: decode → routed add → encode ----
        contrib = jnp.where(admit, acq, 0.0)
        macc = jnp.zeros((P,), jnp.float32)
        for d in range(D):
            k = cur_b * D + jnp.int32(d)
            dma_in = pltpu.make_async_copy(
                counts_ref.at[pl.ds(k, 1)], plane_buf, sem
            )
            dma_in.start()
            dma_in.wait()
            old16 = jnp.where(stale, jnp.int16(0), plane_buf[0])
            x32 = old16.astype(jnp.int32)
            even, lo, hi, merged = _pairs(x32)
            mval = lo + CAP * (-hi - 1)
            # accumulation view: merged value at the even cell only
            dec = jnp.where(merged, jnp.where(even, mval, 0), x32)
            # route adds targeting a merged pair to its even cell
            mrows = jnp.dot(
                oh_slot,
                merged.astype(jnp.float32),
                preferred_element_type=jnp.float32,
                precision=_EXACT,
            )  # [N, C]
            flag = jnp.sum(mrows * oh_idx[d], axis=1) > 0.5  # [N]
            idx_d = idx_ref[:, d]
            idx_eff = jnp.where(flag, (idx_d // 2) * 2, idx_d)
            oh_eff = (
                idx_eff[:, None]
                == jax.lax.broadcasted_iota(jnp.int32, (N, C), 1)
            ).astype(jnp.float32)
            delta = jnp.dot(
                oh_slot.T,
                oh_eff * contrib[:, None],
                preferred_element_type=jnp.float32,
                precision=_EXACT,
            )  # [P, C]
            dec = dec + delta.astype(jnp.int32)
            # re-encode with merge-on-saturation
            p2 = jnp.where(
                even, jnp.roll(dec, -1, axis=1), jnp.roll(dec, 1, axis=1)
            )
            ev = jnp.where(even, dec, p2)
            od = jnp.where(even, p2, dec)
            newly = (~merged) & ((ev > SAT) | (od > SAT))
            m2 = merged | newly
            val = jnp.where(newly, jnp.maximum(ev, od), ev)
            val = jnp.minimum(val, MERGE_CEIL)
            out = jnp.where(
                m2, jnp.where(even, val % CAP, -(val // CAP) - 1), dec
            )
            plane_buf[0] = out.astype(jnp.int16)
            macc = macc + jnp.sum(
                (newly & even).astype(jnp.float32), axis=1
            )
            dma_out = pltpu.make_async_copy(
                plane_buf, counts_out_ref.at[pl.ds(k, 1)], sem
            )
            dma_out.start()
            dma_out.wait()

        admit_ref[:, 0] = admit.astype(jnp.int32)
        est_ref[:, 0] = est.astype(jnp.int32)
        merges_ref[:, 0] = macc.astype(jnp.int32)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=(
        "P", "B", "D", "C", "bucket_ms", "refine_iters", "interpret",
    ),
)
def salsa_decide_update_pallas(
    counts: jax.Array,  # [B*D, P, C] int16
    starts: jax.Array,  # [B] int32
    rule_slot: jax.Array,  # [N] int32 (-1 → no rule)
    idx: jax.Array,  # [N, D] int32 cell indices over C lanes
    acquire: jax.Array,  # [N] int32
    threshold: jax.Array,  # [N] float32
    valid: jax.Array,  # [N] bool
    now: jax.Array,  # int32 scalar
    *,
    P: int,
    B: int,
    D: int,
    C: int,
    bucket_ms: int,
    refine_iters: int = 3,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """``-> (counts', starts', admit [N] bool, estimate [N] int32,
    merge_delta [P] int32)``."""
    N = rule_slot.shape[0]
    if N > MAX_BATCH:
        raise ValueError(f"param batch {N} exceeds pallas cap {MAX_BATCH}")
    # the same sum as ops/cms_pallas.py, on this kernel's int16 plane
    if not interpret:
        refuse_past_vmem(N, P, D, C, _VMEM_LIMIT, cell_bytes=2)
    if refine_iters % 2 == 0:
        raise ValueError("refine_iters must be odd (no-overshoot guarantee)")

    kernel = _make_kernel(P, B, D, C, bucket_ms, refine_iters)
    counts_out, starts_out, admit, est, merges = pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B * D, P, C), jnp.int16),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
            jax.ShapeDtypeStruct((P, 1), jnp.int32),
        ),
        input_output_aliases={0: 0},
        scratch_shapes=[
            pltpu.VMEM((1, P, C), jnp.int16),
            pltpu.SemaphoreType.DMA,
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * N * P * C * D * (B + 2) + 2 * refine_iters * N * N,
            bytes_accessed=2 * P * C * (B * D + 2 * D),
            transcendentals=0,
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(
        counts,
        starts.reshape(B, 1).astype(jnp.int32),
        jnp.asarray(now, jnp.int32).reshape(1, 1),
        rule_slot.reshape(N, 1).astype(jnp.int32),
        idx.astype(jnp.int32),
        acquire.reshape(N, 1).astype(jnp.int32),
        threshold.reshape(N, 1).astype(jnp.float32),
        valid.reshape(N, 1).astype(jnp.int32),
    )
    return (
        counts_out,
        starts_out[:, 0],
        admit[:, 0] != 0,
        est[:, 0],
        merges[:, 0],
    )
