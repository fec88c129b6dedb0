"""SALSA self-adjusting counters for the param sketch (arXiv:2102.12531).

Same HBM bytes as the plain int32 CMS, twice the cells: ``counts`` becomes
``[P, B, depth, 2*width]`` **int16**. Cold traffic enjoys 2× the key
cardinality; when a cell saturates, it merges with its pair neighbor into
one double-width logical counter, degrading resolution only where the
counts are hot enough not to need it.

The merge state is encoded **in-band** — no side bitmaps to allocate, ship,
or keep in sync with serialization:

- unmerged pair ``(2p, 2p+1)``: two independent int16 counters, each held
  below ``SAT`` (merge threshold) by the merge-after-batch discipline;
- merged pair: the logical value ``v`` is split as ``cells[2p] = v % CAP``
  and ``cells[2p+1] = -(v // CAP) - 1`` — the negative high half *is* the
  merge flag (live counters are never negative), giving ``CAP * 32767``
  (~134M) of headroom per merged pair.

Updates and queries stay pure gather/scatter plus elementwise fixups over
the current-bucket plane, so the XLA core below and the Pallas kernel in
``ops/salsa_pallas.py`` share the exact same decide/update semantics as the
plain CMS paths. One-sidedness: a merge stores ``max`` of the two cells
(each an upper bound of its own key set, so the max upper-bounds the
union), the bucket roll zeroes int16 cells exactly like int32 ones, and
saturating arithmetic only ever clamps at the ~134M ceiling — far above any
admissible window threshold (docs/SKETCHES.md).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

LOGCAP = 12
CAP = 1 << LOGCAP  # low-half radix of a merged pair
SAT = 1 << 14  # merge threshold: cell > SAT after a batch ⇒ merge its pair
MERGE_CEIL = CAP * 32767 - 1  # merged-pair clamp (~134M)


def _interleave(even, odd):
    """[..., W], [..., W] -> [..., 2W] with even/odd lanes restored."""
    return jnp.stack([even, odd], axis=-1).reshape(
        even.shape[:-1] + (even.shape[-1] * 2,)
    )


def decode_plane(cells: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``[..., 2W] int16 -> (dec [..., 2W] int32, merged [..., W] bool)``.

    Scatter-accumulation form: a merged pair carries its full logical value
    at the EVEN cell (odd cell decodes to 0), so routed adds accumulate in
    one place and re-encoding is a pure elementwise split.
    """
    c = cells.astype(jnp.int32)
    lo, hi = c[..., 0::2], c[..., 1::2]
    merged = hi < 0
    mval = lo + CAP * (-hi - 1)
    even = jnp.where(merged, mval, lo)
    odd = jnp.where(merged, 0, hi)
    return _interleave(even, odd), merged


def encode_plane(dec: jax.Array,
                 merged: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Inverse of :func:`decode_plane` plus merge-on-saturation.

    ``-> (cells int16, newly_merged [..., W] bool)``. An unmerged pair with
    either side above ``SAT`` merges, taking ``max`` of the two (both are
    upper bounds of their own key sets; max upper-bounds the union, so no
    key ever undercounts).
    """
    ev, od = dec[..., 0::2], dec[..., 1::2]
    newly = (~merged) & ((ev > SAT) | (od > SAT))
    m2 = merged | newly
    val = jnp.where(newly, jnp.maximum(ev, od), ev)
    val = jnp.minimum(val, MERGE_CEIL)
    lo16 = jnp.where(m2, val % CAP, ev).astype(jnp.int16)
    hi16 = jnp.where(m2, -(val // CAP) - 1, od).astype(jnp.int16)
    return _interleave(lo16, hi16), newly


def decode_cells_np(cells: np.ndarray) -> np.ndarray:
    """Host mirror for export paths: ``[..., 2W] int16 -> [..., 2W] int32``
    per-cell *query* values — both cells of a merged pair read the merged
    value, exactly what a gather at either index would see."""
    c = cells.astype(np.int64)
    lo, hi = c[..., 0::2], c[..., 1::2]
    merged = hi < 0
    mval = lo + CAP * (-hi - 1)
    even = np.where(merged, mval, lo)
    odd = np.where(merged, mval, hi)
    out = np.empty(c.shape, np.int32)
    out[..., 0::2] = even
    out[..., 1::2] = odd
    return out


@partial(jax.jit, static_argnames=("config",))
def salsa_decide_jax(
    config, state, rule_slot, idx, acquire, threshold, valid, now
):
    """Same contract as ``engine.param._param_decide_jax`` over the SALSA
    encoding: gathers decode pairwise in-flight; the update decodes the
    current-bucket plane, scatter-adds with merged pairs routed to their
    even cell, and re-encodes with merge-on-saturation. ``state.merges``
    accumulates newly merged pairs per slot."""
    from sentinel_tpu.engine.prefix import segment_prefix_builder

    now = jnp.asarray(now, jnp.int32)
    B = config.n_buckets
    cur_idx = (now // config.bucket_ms) % B
    cur_start = now - now % config.bucket_ms

    stale = state.starts[cur_idx] != cur_start
    counts = jnp.where(
        (jnp.arange(B)[None, :, None, None] == cur_idx) & stale,
        0,
        state.counts,
    )  # zeroed int16 cells are unmerged zeros — the roll clears merge state
    starts = state.starts.at[cur_idx].set(cur_start)

    age = now - starts
    bucket_ok = (age >= 0) & (age < config.interval_ms)  # [B]

    safe_slot = jnp.where(rule_slot >= 0, rule_slot, 0)
    live = valid & (rule_slot >= 0)
    d_ar = jnp.arange(config.depth)[None, :]  # [1, D]
    pair = (idx // 2) * 2  # [N, D] even cell of each index's pair

    def gather_dec(b):
        # decode only the gathered pairs: two int16 gathers per lane
        lo = counts[safe_slot[:, None], b, d_ar, pair].astype(jnp.int32)
        hi = counts[safe_slot[:, None], b, d_ar, pair + 1].astype(jnp.int32)
        merged = hi < 0
        mval = lo + CAP * (-hi - 1)
        own = jnp.where(idx % 2 == 0, lo, hi)
        return jnp.where(merged, mval, own) * bucket_ok[b].astype(jnp.int32)

    sums = sum(gather_dec(b) for b in range(B))  # [N, D]
    estimate = jnp.min(sums, axis=1)  # [N]

    # in-batch prefix admission — identical discipline to the cms core
    key = safe_slot
    for d in range(config.depth):
        key = key * jnp.int32(-1640531527) + idx[:, d]
    seg_prefix = segment_prefix_builder(key, "sort")
    acq = acquire.astype(jnp.int32)
    admit = live
    for _ in range(3):  # odd refinement ⇒ never overshoot (see decide.py)
        contrib = jnp.where(admit, acq, 0)
        prefix = seg_prefix(contrib)
        admit = live & (
            estimate.astype(jnp.float32) + prefix + acq.astype(jnp.float32)
            <= threshold
        )

    # update: decode current plane → routed scatter → re-encode (merges)
    cur_plane = jnp.take(counts, cur_idx, axis=1)  # [P, D, 2W] int16
    dec_cur, merged_cur = decode_plane(cur_plane)  # int32 / [P, D, W] bool
    m_req = merged_cur[safe_slot[:, None], d_ar, idx // 2]  # [N, D]
    idx_eff = jnp.where(m_req, pair, idx)
    upd_vals = jnp.where(admit, acq, 0)[:, None].repeat(config.depth, 1)
    dec_cur = dec_cur.at[
        safe_slot[:, None], d_ar, idx_eff
    ].add(upd_vals, mode="drop")
    new_plane, newly = encode_plane(dec_cur, merged_cur)
    counts = counts.at[:, cur_idx].set(new_plane)
    merges = state.merges + newly.sum(axis=(1, 2)).astype(jnp.int32)

    return (
        state._replace(starts=starts, counts=counts, merges=merges),
        admit,
        estimate,
    )


@partial(jax.jit, static_argnames=("config",))
def salsa_decide_pallas(
    config, state, rule_slot, idx, acquire, threshold, valid, now
):
    """SALSA via the VMEM-resident one-hot-matmul kernel
    (``ops/salsa_pallas.py``); plane-major ``[B*D, P, 2W]`` at the
    boundary, exactly like the cms pallas wrapper."""
    from sentinel_tpu.ops.salsa_pallas import salsa_decide_update_pallas

    P, B, D = config.max_param_rules, config.n_buckets, config.depth
    C = config.cell_width  # 2W int16 cells
    planes = jnp.transpose(state.counts, (1, 2, 0, 3)).reshape(B * D, P, C)
    planes, starts, admit, est, merge_delta = salsa_decide_update_pallas(
        planes,
        state.starts,
        rule_slot,
        idx,
        acquire,
        threshold,
        valid,
        now,
        P=P,
        B=B,
        D=D,
        C=C,
        bucket_ms=config.bucket_ms,
    )
    counts = jnp.transpose(planes.reshape(B, D, P, C), (2, 0, 1, 3))
    return (
        state._replace(
            starts=starts, counts=counts, merges=state.merges + merge_delta
        ),
        admit,
        est,
    )
