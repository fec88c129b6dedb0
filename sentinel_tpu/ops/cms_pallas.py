"""Count-min-sketch decide+update as a single Pallas TPU kernel.

Semantics match ``engine.param.param_decide`` (the windowed-CMS re-design of
``ClusterParamFlowChecker.java:42-96`` / ``ParameterMetric.java`` — see
``engine/param.py``): roll the current time bucket, estimate each request's
windowed count (min over depth lanes), admit greedily against the threshold
with in-batch prefix refinement, scatter admitted acquires into the current
bucket's lanes.

Kernel design (vs. the pure-XLA fallback):

- The sketch lives in HBM as ``[B*D, P, W]``; each (bucket, depth) plane
  ``[P, W]`` is DMA'd into one VMEM scratch buffer on demand. Only the D
  current-bucket planes are written back — the roll's "zero a stale bucket"
  is folded into the write (replace instead of add), so stale planes are
  never even read twice.
- Gathers (``counts[slot, b, d, idx]``) and scatters become one-hot MXU
  matmuls: ``onehot(slot) @ plane`` → per-request rows, then a masked
  row-dot with ``onehot(idx)``; the update is ``onehot(slot)ᵀ @
  (onehot(idx) * contrib)``. XLA's TPU scatter lowers to a serialized loop;
  this is ~N·P·W MACs on the systolic array instead.
- The in-batch admission refinement is the same odd-iteration-count prefix
  loop as the fallback (subset-of-greedy guarantee, ``engine/decide.py``),
  with the [N, N] same-key mask built in VMEM (N is capped so it fits).

Backend selection: Mosaic compiles this kernel for the TPU only, so
``ParamConfig(impl="auto")`` (the default) resolves to the XLA path anywhere
else; on TPU the two are micro-probed once per process and the faster wins.
See ``engine.param.explain_param_impl`` — pin explicitly with ``impl=`` or
the ``SENTINEL_PARAM_IMPL`` env var. Selected, the kernel is compiled or it
raises; only the CPU parity tests ask for ``interpret=True``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The kernel's VMEM stack grows with N: the [N, N] f32 prefix mask, D
# [N, W] one-hots and the gathered [N, W] rows next to a [P, W] plane. At
# the default ParamConfig that is ~23 MB at N=1024 — past Mosaic's 16 MiB
# default scoped limit, which refuses the kernel with RESOURCE_EXHAUSTED —
# so the call asks for 64 MiB (a v5e core has 128 MiB of VMEM).
MAX_BATCH = 1024
_VMEM_LIMIT = 64 * 1024 * 1024


def vmem_bytes(N: int, P: int, D: int, W: int, cell_bytes: int = 4) -> int:
    """About what the kernel keeps in VMEM at once: the ``[N, N]`` f32 mask,
    ``D`` ``[N, W]`` one-hots beside the gathered ``[N, W]`` rows, and one
    ``[P, W]`` plane of the sketch. A geometry past ``_VMEM_LIMIT`` is
    refused by this sum, before Mosaic is asked: at ``hot-param-1k``'s 1,024
    rules x 16,384 cells the plane alone is the whole limit, and the
    compiler does not come back with its refusal within ten minutes
    (PERF.md section 6, PR 45)."""
    return 4 * N * N + 4 * (D + 1) * N * W + cell_bytes * P * W


def refuse_past_vmem(N: int, P: int, D: int, W: int, limit: int,
                     cell_bytes: int = 4) -> None:
    need = vmem_bytes(N, P, D, W, cell_bytes)
    if need > limit:
        raise ValueError(
            f"param sketch {P} x {D} x {W} at {N} rows needs about "
            f"{need >> 20} MiB of VMEM; the pallas kernel's limit is "
            f"{limit >> 20} MiB")

# The one-hot matmuls carry integer counts through the MXU; its default
# single bf16 pass keeps 8 mantissa bits (cells above 256 would round).
# HIGHEST keeps integer-valued f32 exact up to 2^24.
_EXACT = jax.lax.Precision.HIGHEST


def _make_kernel(P: int, B: int, D: int, W: int, bucket_ms: int, refine_iters: int):
    interval_ms = bucket_ms * B

    def kernel(
        counts_ref,  # ANY [B*D, P, W] int32 (aliased to counts_out_ref)
        starts_ref,  # SMEM [B, 1] int32
        now_ref,  # SMEM [1, 1] int32
        slot_ref,  # VMEM [N, 1] int32
        idx_ref,  # VMEM [N, D] int32
        acq_ref,  # VMEM [N, 1] int32
        thr_ref,  # VMEM [N, 1] float32
        valid_ref,  # VMEM [N, 1] int32
        counts_out_ref,  # ANY [B*D, P, W] int32
        starts_out_ref,  # SMEM [B, 1] int32
        admit_ref,  # VMEM [N, 1] int32
        est_ref,  # VMEM [N, 1] int32
        plane_buf,  # VMEM scratch [1, P, W] int32
        sem,  # DMA semaphore
    ):
        N = slot_ref.shape[0]
        now = now_ref[0, 0]
        cur_b = (now // bucket_ms) % B
        cur_start = now - now % bucket_ms

        # roll bookkeeping — static unroll over the (tiny) bucket ring
        stale = jnp.bool_(False)
        for b in range(B):
            is_cur = jnp.int32(b) == cur_b
            stale = jnp.where(is_cur, starts_ref[b, 0] != cur_start, stale)
            starts_out_ref[b, 0] = jnp.where(is_cur, cur_start, starts_ref[b, 0])

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
                == jax.lax.broadcasted_iota(jnp.int32, (N, W), 1)
            ).astype(jnp.float32)
            for d in range(D)
        ]
        acq = acq_ref[:, 0].astype(jnp.float32)

        # ---- estimate: min over depth of windowed per-cell sums ----
        est = None
        for d in range(D):
            acc = jnp.zeros((N,), jnp.float32)
            for b in range(B):
                start_b = starts_out_ref[b, 0]
                age = now - start_b
                ok = (age >= 0) & (age < interval_ms)
                # a stale current bucket is logically zero until rewritten
                ok = ok & ~(stale & (jnp.int32(b) == cur_b))
                dma = pltpu.make_async_copy(
                    counts_ref.at[pl.ds(b * D + d, 1)], plane_buf, sem
                )
                dma.start()
                dma.wait()
                rows = jnp.dot(
                    oh_slot,
                    plane_buf[0].astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                    precision=_EXACT,
                )  # [N, W]
                cell = jnp.sum(rows * oh_idx[d], axis=1)
                acc = acc + jnp.where(ok, cell, 0.0)
            est = acc if est is None else jnp.minimum(est, acc)

        # ---- in-batch prefix admission (odd refinement ⇒ ⊆ greedy-exact) ----
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

        # ---- update the D current-bucket planes (replace-on-stale = roll) ----
        contrib = jnp.where(admit, acq, 0.0)
        for d in range(D):
            k = cur_b * D + jnp.int32(d)
            dma_in = pltpu.make_async_copy(
                counts_ref.at[pl.ds(k, 1)], plane_buf, sem
            )
            dma_in.start()
            dma_in.wait()
            old = jnp.where(stale, 0, plane_buf[0])
            delta = jnp.dot(
                oh_slot.T,
                oh_idx[d] * contrib[:, None],
                preferred_element_type=jnp.float32,
                precision=_EXACT,
            )  # [P, W]
            plane_buf[0] = old + delta.astype(jnp.int32)
            dma_out = pltpu.make_async_copy(
                plane_buf, counts_out_ref.at[pl.ds(k, 1)], sem
            )
            dma_out.start()
            dma_out.wait()

        admit_ref[:, 0] = admit.astype(jnp.int32)
        est_ref[:, 0] = est.astype(jnp.int32)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("P", "B", "D", "W", "bucket_ms", "refine_iters", "interpret"),
)
def cms_decide_update_pallas(
    counts: jax.Array,  # [B*D, P, W] int32
    starts: jax.Array,  # [B] int32
    rule_slot: jax.Array,  # [N] int32 (-1 → no rule)
    idx: jax.Array,  # [N, D] int32 CMS cell indices
    acquire: jax.Array,  # [N] int32
    threshold: jax.Array,  # [N] float32
    valid: jax.Array,  # [N] bool
    now: jax.Array,  # int32 scalar
    *,
    P: int,
    B: int,
    D: int,
    W: int,
    bucket_ms: int,
    refine_iters: int = 3,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``-> (counts', starts', admit [N] bool, estimate [N] int32)``."""
    N = rule_slot.shape[0]
    if N > MAX_BATCH:
        raise ValueError(f"param batch {N} exceeds pallas cap {MAX_BATCH}")
    if not interpret:
        refuse_past_vmem(N, P, D, W, _VMEM_LIMIT)
    if refine_iters % 2 == 0:
        raise ValueError("refine_iters must be odd (no-overshoot guarantee)")

    kernel = _make_kernel(P, B, D, W, bucket_ms, refine_iters)
    counts_out, starts_out, admit, est = pl.pallas_call(
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
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B * D, P, W), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
        ),
        input_output_aliases={0: 0},
        scratch_shapes=[
            pltpu.VMEM((1, P, W), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * N * P * W * D * (B + 1) + 2 * refine_iters * N * N,
            bytes_accessed=4 * P * W * (B * D + 2 * D),
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
    return counts_out, starts_out[:, 0], admit[:, 0] != 0, est[:, 0]
