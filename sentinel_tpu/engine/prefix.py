"""Exclusive segment-prefix-sum over batch order — shared by the flow and
param kernels (the in-batch "earlier same-key contributions" primitive).

Five implementations (measured on a v5e chip; all scan-free — cumulative
sums and maxes go through ``sentinel_tpu.ops.scan_mm`` blocked matmul /
reduce passes because XLA's 1-D scan lowering costs ~0.3ms at N=16k):

- ``matmul``: same-key strictly-lower mask @ contrib — one [N, N] masked
  matmul, nearly free on the MXU up to N≈4k but the mask materialization
  grows quadratically.
- ``sort``: one stable argsort per builder (shared by every call), then per
  call a gather + blocked cumsum + segment rebase + scatter-back. Stable
  sort preserves batch order within a segment, which greedy-admission
  semantics require.
- ``grouped``: the keys are already **grouped** (same-key rows contiguous —
  e.g. the host batcher sorted requests by flow slot); no device sort at
  all, just the cumsum + rebase. This is the serving fast path.
- ``pallas``: the tiled kernel in ``ops/prefix_pallas.py`` — same math as
  ``matmul`` but the [N, N] mask is built tile-by-tile in VMEM and never
  touches HBM (compiled by Mosaic: TPU only).

Contributions must be **non-negative** float32 (exact for counts < 2^24):
the segment rebase recovers each row's segment-head offset with a running
max over head-marked exclusive sums, which requires the exclusive sum to be
non-decreasing. Every caller feeds masked non-negative counts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sentinel_tpu.ops.scan_mm import blocked_cumsum, blocked_cummax

_IMPLS = ("matmul", "sort", "grouped", "pallas")


def _grouped_prefix(keys: jax.Array):
    """Prefix fn for keys whose equal values are contiguous in batch order."""
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), keys[1:] != keys[:-1]]
    )

    def prefix(c: jax.Array) -> jax.Array:
        c = c.astype(jnp.float32)
        incl = blocked_cumsum(c)
        excl = incl - c
        # exclusive sum at this row's segment head: heads carry their excl,
        # a running max propagates the latest head forward (valid because
        # contribs >= 0 keeps excl non-decreasing)
        base = blocked_cummax(jnp.where(seg_start, excl, -1.0))
        return excl - base

    return prefix


def segment_prefix_builder(keys: jax.Array, impl: str = "auto"):
    """Returns ``prefix(contrib)`` with
    ``prefix(contrib)[i] = sum(contrib[j] for j < i if keys[j] == keys[i])``.

    (The namespace axis uses an inline one-hot cumsum in ``decide`` instead
    of this builder — its one-hot matrix is reused for the guard-counter
    matvec, which a builder-shaped API can't share.)
    """
    n = keys.shape[0]
    if impl == "auto":
        impl = "matmul" if n <= 2048 else "sort"
    if impl not in _IMPLS:
        raise ValueError(
            f"unknown prefix_impl {impl!r}; use 'auto' or one of {_IMPLS}"
        )

    if impl == "grouped":
        return _grouped_prefix(keys)

    if impl == "pallas":
        from sentinel_tpu.ops.prefix_pallas import segment_prefix_pallas

        def prefix_pallas(contrib: jax.Array) -> jax.Array:
            return segment_prefix_pallas(keys, contrib)

        return prefix_pallas

    if impl == "matmul":
        i = jnp.arange(n)
        tri = i[:, None] > i[None, :]
        mat = ((keys[:, None] == keys[None, :]) & tri).astype(jnp.float32)

        def prefix_mat(contrib: jax.Array) -> jax.Array:
            return jnp.matmul(
                mat, contrib.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,  # exact integer counts
            )

        return prefix_mat

    # -- sort -------------------------------------------------------------
    # One argsort per builder, shared by every call (decide() makes up to 5
    # on one builder); the inverse permutation is a scatter of the identity,
    # not a second argsort.
    order = jnp.argsort(keys, stable=True)
    arange = jnp.arange(n)
    inv = jnp.zeros((n,), arange.dtype).at[order].set(arange)
    grouped = _grouped_prefix(keys[order])

    def prefix_sort(contrib: jax.Array) -> jax.Array:
        return grouped(contrib[order])[inv]

    return prefix_sort
