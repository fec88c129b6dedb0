"""The count-min sketch's commit: add a batch of ``(cell, amount)`` pairs
into the flat cells, touching each row of 128 cells once.

XLA's scatter on the TPU applies the updates of a batch one after the other
against HBM, 92-110 ns a cell, whatever it is told about them
(``unique_indices`` changes nothing, ``indices_are_sorted`` makes it a pass
over the whole operand, 1.6 ms for the 512 MiB of ``hot-param-1k``; PERF.md
section 6, PR 39). This kernel does what the hints asked for, at 36 ns a
cell. The batch is cut into chunks of ``CHUNK`` pairs and each chunk is
sorted by cell; the sketch is seen as rows of 128 cells (a bitcast of the
flat array: a row is 512 contiguous bytes). A *run* is the pairs of one
chunk that share a sketch row, duplicates of one cell among them. Per chunk:

1. every pair becomes a row of 128 with its amount in its cell's lane, and
   doubling steps down the chunk sum each run into its last pair (integer
   adds: exact whatever the order; all of them every time: stopping at
   the chunk's longest run saved 2 us of 137 and was taken out again);
2. every pair starts a DMA of its run's sketch row into its run's slot of a
   VMEM buffer, all of them in flight together, then waits for it. The
   pairs of one run move the same bytes to the same place; what that costs
   is less than the branch that would skip them (a row read and written
   back is 28 ns when every pair does it, 61 ns behind a branch a pair);
3. one vector add of the run totals, and the same DMAs the other way.

A sketch row that two chunks meet is committed twice, the second time after
the first has landed: the grid is sequential and a chunk waits for its
writes. Pairs whose cell lies past the end of the sketch sort behind their
chunk's others and are dropped (refused and padding rows cost nothing), and
a chunk of nothing else does no work.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
CHUNK = 256
UNROLL = 8


def _kernel(run_row, run_end, n_live, amounts, run_key, _cells_in, cells,
            buf, sem, *, chunk):
    base = pl.program_id(0) * chunk
    live = n_live[pl.program_id(0)]

    @pl.when(live > 0)
    def _():
        groups = (live + UNROLL - 1) // UNROLL

        def sketch_row(r):
            return cells.at[pl.ds(r, 1), :]

        def slot(j):
            return buf.at[pl.ds(j, 1), :]

        def for_pairs(s, copy, do):
            """``do`` the copy of every live pair's run, eight a turn and
            no branch among them: the turn past the last live pair repeats
            that pair's copy, which moves the same bytes again."""
            def eight(g, carry):
                for u in range(UNROLL):
                    i = base + jnp.minimum(g * UNROLL + u, live - 1)
                    do(copy(run_row[i], run_end[i], sem.at[s]))
                return carry

            lax.fori_loop(0, groups, eight, 0)

        def read(r, j, sem):
            return pltpu.make_async_copy(sketch_row(r), slot(j), sem)

        def write(r, j, sem):
            return pltpu.make_async_copy(slot(j), sketch_row(r), sem)

        for_pairs(0, read, lambda c: c.start())
        total = amounts[...]
        key = run_key[...]
        at = lax.broadcasted_iota(jnp.int32, total.shape, 0)
        step = 1
        while step < chunk:
            same = (key == pltpu.roll(key, step, 0)) & (at >= step)
            total = total + jnp.where(same, pltpu.roll(total, step, 0), 0)
            step *= 2
        for_pairs(0, read, lambda c: c.wait())
        buf[...] = buf[...] + total
        for_pairs(1, write, lambda c: c.start())
        for_pairs(1, write, lambda c: c.wait())


def commit_cells(counts, cell, amount, *, interpret=False):
    """``counts`` flat ``int32[size]`` with ``counts[cell[i]] += amount[i]``
    for every ``i`` with ``cell[i] < size``, duplicates and all; the others
    are dropped. ``cell`` and ``amount`` are ``int32[n]`` in any order."""
    size = counts.shape[0]
    n = cell.shape[0]
    chunk = min(CHUNK, -(-n // UNROLL) * UNROLL)
    pad = -n % chunk
    if pad:
        cell = jnp.concatenate([cell, jnp.full((pad,), size, cell.dtype)])
        amount = jnp.concatenate([amount, jnp.zeros((pad,), amount.dtype)])
        n += pad
    # sorted chunk by chunk: a run has to be one stretch of its chunk and no
    # more (the chunks go one after the other), and the whole batch in one
    # sort compiles for 19 s at 16,384 rows
    cell, amount = lax.sort(
        (cell.reshape(-1, chunk), amount.reshape(-1, chunk)), num_keys=1)
    live = cell < size
    n_live = jnp.sum(live.astype(jnp.int32), axis=1)
    row = jnp.where(live, cell // LANES, -1)
    at = lax.broadcasted_iota(jnp.int32, row.shape, 1)
    ends_run = (at == chunk - 1) | (jnp.roll(row, -1, axis=1) != row)
    # where in its chunk each pair's run ends: the next end at or after it
    run_end = lax.cummin(jnp.where(ends_run, at, chunk), axis=1,
                         reverse=True).reshape(-1)
    cell, amount, row = (x.reshape(-1) for x in (cell, amount, row))
    # a dropped pair keeps its amount: its run is of dropped pairs alone
    # (row -1) and no copy ever names its slot
    lane = lax.broadcasted_iota(jnp.int32, (n, LANES), 1)
    amounts = jnp.where(lane == (cell % LANES)[:, None], amount[:, None], 0)
    sketch_rows = -(-size // LANES)
    flat_pad = sketch_rows * LANES - size
    if flat_pad:
        counts = jnp.concatenate([counts, jnp.zeros((flat_pad,),
                                                    counts.dtype)])
    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((sketch_rows, LANES), counts.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // chunk,),
            in_specs=[pl.BlockSpec((chunk, LANES), lambda c, *_: (c, 0)),
                      pl.BlockSpec((chunk, LANES), lambda c, *_: (c, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((chunk, LANES), counts.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        input_output_aliases={5: 0},
        interpret=interpret,
    )(row, run_end, n_live, amounts,
      jnp.broadcast_to(row[:, None], (n, LANES)),
      counts.reshape(sketch_rows, LANES))
    out = out.reshape(-1)
    return out[:size] if flat_pad else out
