"""Sketch variants for the hot-param plane, behind one interface.

``ParamConfig.sketch`` selects the fat (update) sketch — ``"cms"`` (the
seed's plain int32 count-min) or ``"salsa"`` (:mod:`sentinel_tpu.sketch.salsa`,
int16 self-adjusting counters at the same HBM bytes) — and
``ParamConfig.impl`` independently selects the kernel ("jax" | "pallas" |
"auto", probed by ``engine.param.explain_param_impl``). The SF slim twin
(:mod:`sentinel_tpu.sketch.slim`) composes around either variant; the
accuracy harness (:mod:`sentinel_tpu.sketch.parity`) proves every
combination keeps the one-sided (never-undercount) guarantee.

This module holds the variant-dispatch helpers the cluster service needs
outside the decide kernels: post-update current-bucket estimate gathers
(slim maintenance), MOVE-import folds, host-side decoding for exports, and
the metrics snapshot.
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

VARIANTS = ("cms", "salsa")


def gather_current_estimate(config, counts, rule_slot, idx, cur_idx):
    """``[N] int32`` per-request fat estimate over the CURRENT bucket only
    (min over depth lanes), decoding in-flight for SALSA. Traced inside the
    slim post-step jit."""
    from sentinel_tpu.sketch.salsa import CAP

    safe_slot = jnp.where(rule_slot >= 0, rule_slot, 0)
    d_ar = jnp.arange(config.depth)[None, :]
    if config.sketch == "salsa":
        pair = (idx // 2) * 2
        lo = counts[safe_slot[:, None], cur_idx, d_ar, pair].astype(jnp.int32)
        hi = counts[safe_slot[:, None], cur_idx, d_ar, pair + 1].astype(
            jnp.int32
        )
        merged = hi < 0
        mval = lo + CAP * (-hi - 1)
        own = jnp.where(idx % 2 == 0, lo, hi)
        per_d = jnp.where(merged, mval, own)
    elif counts.ndim == 1:
        # the serve step's flat cells (engine.param.make_param_step)
        B, W = config.n_buckets, config.width
        per_d = counts[
            ((safe_slot[:, None] * B + cur_idx) * config.depth + d_ar) * W
            + idx
        ]
    else:
        per_d = counts[safe_slot[:, None], cur_idx, d_ar, idx]
    return jnp.min(per_d, axis=1)


def decoded_counts_np(config, counts: np.ndarray) -> np.ndarray:
    """Host view of the fat cells as per-cell *query* values: identity for
    cms, pairwise decode for SALSA (both cells of a merged pair read the
    merged value). Exports sum these — the wire document stays plain int
    sums whatever the in-memory encoding is."""
    if config.sketch == "salsa":
        from sentinel_tpu.sketch.salsa import decode_cells_np

        return decode_cells_np(np.asarray(counts))
    return np.asarray(counts)


def fold_param_sums(config, state, now: int, rows, sums):
    """Sketch-aware analog of ``token_service._fold_into_current`` for the
    param plane: pre-rotate a stale current bucket (fat column, slim column,
    and the bucket's slim-authority flag), then add the imported per-cell
    window sums into the current bucket. For SALSA the add happens in
    decoded space — merged pairs absorb both cells' sums into the joint
    counter (conservative: the union bound) — and re-encoding applies the
    usual merge-on-saturation, counted into ``state.merges``."""
    from sentinel_tpu.sketch.salsa import CAP, MERGE_CEIL, SAT

    B = config.n_buckets
    idx = int((now // config.bucket_ms) % B)
    aligned = int(now - now % config.bucket_ms)
    starts = np.asarray(state.starts)
    counts, slim = state.counts, state.slim
    slim_auth, merges = state.slim_auth, state.merges
    if int(starts[idx]) != aligned:
        counts = counts.at[:, idx].set(0)
        if config.slim_enabled:
            slim = slim.at[:, idx].set(0)
        slim_auth = slim_auth.at[idx].set(False)
        starts = np.array(starts)
        starts[idx] = aligned
    if rows is not None and len(rows):
        rows = np.asarray(rows, np.int32)
        sums = np.asarray(sums)
        if config.sketch == "salsa":
            plane = np.asarray(counts)[:, idx]  # [P, D, 2W] int16
            c = plane.astype(np.int64)
            lo, hi = c[..., 0::2], c[..., 1::2]
            merged = hi < 0
            mval = lo + CAP * (-hi - 1)
            ev = np.where(merged, mval, lo)
            od = np.where(merged, 0, hi)
            add = sums.astype(np.int64)
            add_ev, add_od = add[..., 0::2], add[..., 1::2]
            mrow = merged[rows]
            ev_r = ev[rows] + np.where(mrow, add_ev + add_od, add_ev)
            od_r = od[rows] + np.where(mrow, 0, add_od)
            newly = (~mrow) & ((ev_r > SAT) | (od_r > SAT))
            m2 = mrow | newly
            val = np.where(newly, np.maximum(ev_r, od_r), ev_r)
            val = np.minimum(val, MERGE_CEIL)
            new_rows = np.empty_like(plane[rows])
            new_rows[..., 0::2] = np.where(m2, val % CAP, ev_r).astype(
                np.int16
            )
            new_rows[..., 1::2] = np.where(m2, -(val // CAP) - 1,
                                           od_r).astype(np.int16)
            out = np.array(plane)
            out[rows] = new_rows
            counts = counts.at[:, idx].set(jnp.asarray(out))
            mdelta = np.zeros(config.max_param_rules, np.int32)
            np.add.at(mdelta, rows, newly.sum(axis=(1, 2)).astype(np.int32))
            merges = merges + jnp.asarray(mdelta)
        else:
            counts = counts.at[rows, idx].add(
                jnp.asarray(sums.astype(np.int32))
            )
    return state._replace(
        starts=jnp.asarray(starts),
        counts=counts,
        slim=slim,
        slim_auth=slim_auth,
        merges=merges,
    )


def sketch_stats(config, state) -> Dict[str, object]:
    """Host snapshot for the ``sketch`` observability block
    (``clusterServerStats`` / the Prometheus exporter)."""
    merges = np.asarray(state.merges)
    nz = np.nonzero(merges)[0]
    return {
        "variant": config.sketch,
        # sizes, not contents: never pull the sketch to the host for them
        "fatBytes": int(state.counts.nbytes),
        "slimBytes": int(state.slim.nbytes) if config.slim_enabled else 0,
        "slimEnabled": bool(config.slim_enabled),
        "mergesTotal": int(merges.sum()),
        "mergesBySlot": {int(s): int(merges[s]) for s in nz},
    }
