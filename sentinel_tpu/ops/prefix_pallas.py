"""Tiled Pallas kernel for the exclusive segment prefix sum.

Semantics match ``engine.prefix.segment_prefix_builder``:
``out[i] = sum(contrib[j] for j < i if keys[j] == keys[i])`` — the
"tokens claimed by earlier same-flow requests in this batch" primitive of
the admission kernels (``engine/decide.py`` step 3, ``engine/param.py``).

The pure-XLA ``matmul`` implementation materializes the [N, N] float32
same-key/strictly-lower mask in HBM (1 GB at N=16k). This kernel tiles the
mask: each grid step builds a [TILE_R, TILE_C] block on the fly from two
key slices and accumulates ``block @ contrib_slice`` into the output tile —
O(N) HBM traffic, MXU does the N² MACs.

Padding contract: callers may pass any N; inputs are zero-padded to tile
multiples. Padded *columns* carry contrib 0 so they never contribute;
padded *rows* are sliced off the output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_R = 256
TILE_C = 512
# contributions are integer token counts: keep them exact through the MXU
# (its default bf16 pass rounds values above 256)
_EXACT = jax.lax.Precision.HIGHEST


def _kernel(keys_row_ref, keys_col_ref, contrib_col_ref, out_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    rk = keys_row_ref[:]  # [TILE_R, 1]
    ck = keys_col_ref[:]  # [TILE_C, 1]
    row_g = i * TILE_R + jax.lax.broadcasted_iota(jnp.int32, (TILE_R, 1), 0)
    col_g = j * TILE_C + jax.lax.broadcasted_iota(jnp.int32, (TILE_C, 1), 0)
    mask = (rk == ck.T) & (row_g > col_g.T)  # [TILE_R, TILE_C]
    out_ref[:] += jnp.dot(
        mask.astype(jnp.float32),
        contrib_col_ref[:],
        preferred_element_type=jnp.float32,
        precision=_EXACT,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def segment_prefix_pallas(
    keys: jax.Array, contrib: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """``([N] int32, [N] float-like) -> [N] float32`` exclusive segment prefix."""
    n = keys.shape[0]
    n_pad = max(TILE_R, TILE_C) * -(-n // max(TILE_R, TILE_C))
    keys_p = jnp.zeros((n_pad, 1), jnp.int32).at[:n, 0].set(keys.astype(jnp.int32))
    contrib_p = (
        jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(contrib.astype(jnp.float32))
    )

    grid = (n_pad // TILE_R, n_pad // TILE_C)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_R, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE_C, 1), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE_C, 1), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (TILE_R, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_pad * n_pad, bytes_accessed=3 * 4 * n_pad, transcendentals=0
        ),
        interpret=interpret,
    )(keys_p, keys_p, contrib_p)
    return out[:n, 0]
