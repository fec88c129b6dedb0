"""Sliding-window counters as ring-indexed device tensors.

This is the TPU-native re-design of the reference's ``LeapArray<T>``
(``sentinel-core/.../slots/statistic/base/LeapArray.java:41``): a circular array
of time buckets where ``idx = (now // bucket_ms) % n_buckets`` and a bucket is
*deprecated* (excluded from reads) once its recorded window start falls outside
``(now - interval, now]``.

Key differences from the JVM design, driven by XLA semantics:

- **One global clock per step.** The reference resets buckets lazily per
  resource with a CAS loop (``LeapArray.java:116-160``) because each thread
  carries its own ``now``. A batched kernel applies a single ``now_ms`` to the
  whole step, so bucket occupancy is *uniform across resources*: the window
  start of ring slot ``b`` is one shared ``starts[b]`` vector, not per-resource
  state. Reset becomes "zero the counts column whose slot is being re-occupied"
  — a masked elementwise op, no CAS.

- **Mask-on-read instead of reset-on-read.** Buckets that went stale during an
  idle gap keep old counts but are excluded by the validity mask
  (``starts[b] in (now - interval, now]``); they are zeroed when their slot is
  next written. Matches ``LeapArray.isWindowDeprecated`` + ``values()`` read
  semantics (``LeapArray.java:257-266``).

- **Engine-relative int32 time.** Timestamps are milliseconds since an
  engine-chosen epoch so they fit int32 without enabling jax x64 (which would
  change dtype defaults for embedding applications). int32 ms wraps after
  ~24.8 days; hosts re-base the epoch with :func:`shift_clock` well before that
  (a single subtraction over ``starts``).

All functions are pure, jit-compatible, and take ``now`` explicitly (the test
lesson from the reference's PowerMock clock fixture, SURVEY.md §4).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

# Sentinel value for "slot never occupied": far in the past relative to any
# engine-relative timestamp (engine time starts near 0).
NEVER = jnp.int32(-(2**30))


class WindowSpec(NamedTuple):
    """Static geometry of a sliding window.

    reference: ``LeapArray(sampleCount, intervalInMs)`` with
    ``windowLengthInMs = intervalInMs / sampleCount`` (``LeapArray.java:61-72``).
    """

    bucket_ms: int
    n_buckets: int

    @property
    def interval_ms(self) -> int:
        return self.bucket_ms * self.n_buckets


class WindowState(NamedTuple):
    """Dynamic window state (a pytree of device arrays).

    ``starts``: ``[n_buckets] int32`` — engine-ms window start currently
    occupying each ring slot (shared across resources; see module docstring).
    ``counts``: ``[n_resources, n_buckets, n_channels]`` int32 (or float32 for
    RT-style accumulators) — per-resource, per-bucket event counters.
    """

    starts: jax.Array
    counts: jax.Array


def make_window(
    spec: WindowSpec, n_resources: int, n_channels: int, dtype=jnp.int32
) -> WindowState:
    return WindowState(
        starts=jnp.full((spec.n_buckets,), NEVER, dtype=jnp.int32),
        counts=jnp.zeros((n_resources, spec.n_buckets, n_channels), dtype=dtype),
    )


def bucket_index(spec: WindowSpec, now: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``(ring slot, window start)`` for time ``now``.

    reference: ``LeapArray.calculateTimeIdx`` / ``calculateWindowStart``
    (``LeapArray.java:100-108``).
    """
    now = jnp.asarray(now, jnp.int32)
    idx = (now // spec.bucket_ms) % spec.n_buckets
    start = now - now % spec.bucket_ms
    return idx, start


def roll(spec: WindowSpec, ws: WindowState, now: jax.Array) -> WindowState:
    """Ensure the ring slot for ``now`` holds the current window (zero if stale).

    Analog of the reset arm of ``LeapArray.currentWindow`` (``LeapArray.java:
    132-160``) — but a data-parallel masked zero instead of a CAS race.
    """
    with jax.named_scope("window_roll"):
        idx, cur_start = bucket_index(spec, now)
        stale = ws.starts[idx] != cur_start
        # scatter-multiply of ONE bucket column ([R, E]) instead of rewriting
        # the whole [R, B, E] tensor — keeps the roll O(R·E) per step
        keep = jnp.where(stale, 0, 1).astype(ws.counts.dtype)
        counts = ws.counts.at[:, idx, :].multiply(keep)
        starts = ws.starts.at[idx].set(cur_start)
        return WindowState(starts=starts, counts=counts)


def _write_current(spec: WindowSpec, ws: WindowState, now: jax.Array, write):
    """Roll, then ``write`` the current bucket's ``[R, E]`` slab: the slab is
    taken out of the ring, handed to ``write`` and put back in place. The
    scatters of :func:`add_event_rows` go through here, so that none sees
    the whole window: the TPU's scatter takes a flat operand and costs a pass
    over it, and on the tiled ``[R, B, E]`` window the compiler would copy
    the window flat, stream all of it through the scatter and copy it back,
    every call, whatever the batch (measured on a v5e at 100k rows: 0.46 of
    a 0.82 ms decide step, PERF.md section 5). On the slab the cost follows
    the batch and one bucket, not the table times the ring."""
    ws = roll(spec, ws, now)
    idx, _ = bucket_index(spec, now)
    slab = jax.lax.dynamic_index_in_dim(ws.counts, idx, axis=1, keepdims=False)
    counts = jax.lax.dynamic_update_index_in_dim(
        ws.counts, write(slab), idx, axis=1
    )
    return WindowState(starts=ws.starts, counts=counts)


def add_events(
    spec: WindowSpec,
    ws: WindowState,
    now: jax.Array,
    resource_ids: jax.Array,
    channel_ids: jax.Array,
    values: jax.Array,
    valid: Optional[jax.Array] = None,
) -> WindowState:
    """Batched scatter-add of ``values`` into the current bucket.

    Replaces the reference's per-request ``bucket.addPass(n)`` LongAdder
    increments (``MetricBucket.java``) with one ``scatter-add``; duplicate
    ``(resource, channel)`` pairs within the batch accumulate correctly.
    """
    ws = roll(spec, ws, now)
    idx, _ = bucket_index(spec, now)
    if valid is not None:
        values = jnp.where(valid, values, 0)
    counts = ws.counts.at[resource_ids, idx, channel_ids].add(
        values.astype(ws.counts.dtype), mode="drop"
    )
    return WindowState(starts=ws.starts, counts=counts)


def add_event_rows(
    spec: WindowSpec,
    ws: WindowState,
    now: jax.Array,
    resource_ids: jax.Array,
    row_updates: jax.Array,
    channels: Optional[Tuple[int, ...]] = None,
) -> WindowState:
    """Scatter-add ``row_updates[i, j]`` ([K, len(channels)]) into channel
    ``channels[j]`` of the current bucket of resource ``resource_ids[i]``.

    One scatter per *static* channel, each into the ``[R]`` column of the
    current bucket's slab (:func:`_write_current`) that it touches, as the
    flat vector it is: about 9 ns a row on a v5e. Adding the channel as a
    second traced index dimension (the 5N-concatenation form) or as a
    scatter update window is 4–10x slower. This is the decision kernel's
    write path. Rows intended as no-ops must carry zero updates (or an
    out-of-range id to drop the row entirely).
    """
    chans = range(row_updates.shape[1]) if channels is None else channels

    def write(slab):
        for j, ch in enumerate(chans):
            column = slab[:, int(ch)].at[resource_ids].add(
                row_updates[:, j].astype(slab.dtype), mode="drop"
            )
            slab = slab.at[:, int(ch)].set(column)
        return slab

    return _write_current(spec, ws, now, write)


def add_column(
    spec: WindowSpec,
    ws: WindowState,
    now: jax.Array,
    deltas: jax.Array,
    channel: int = 0,
) -> WindowState:
    """Add a dense per-resource delta vector ([n_resources]) to one channel of
    the current bucket — for small resource axes (the namespace guard) where
    the deltas are cheaper to materialize densely (one-hot matvec) than to
    scatter row-by-row."""
    ws = roll(spec, ws, now)
    idx, _ = bucket_index(spec, now)
    counts = ws.counts.at[:, idx, channel].add(deltas.astype(ws.counts.dtype))
    return WindowState(starts=ws.starts, counts=counts)


def valid_mask(spec: WindowSpec, ws: WindowState, now: jax.Array) -> jax.Array:
    """``[n_buckets] bool`` — slots whose window is inside ``(now - interval, now]``.

    reference: ``!isWindowDeprecated(time, w)`` i.e.
    ``time - windowStart < intervalInMs`` (``LeapArray.java:250-266``).
    """
    now = jnp.asarray(now, jnp.int32)
    age = now - ws.starts
    return (age >= 0) & (age < spec.interval_ms)


def window_sum(
    spec: WindowSpec, ws: WindowState, now: jax.Array, channel: int
) -> jax.Array:
    """``[n_resources]`` sum of one channel over valid buckets
    (``ArrayMetric.pass_()/block()…`` read path)."""
    mask = valid_mask(spec, ws, now)
    return jnp.sum(
        ws.counts[:, :, channel] * mask[None, :].astype(ws.counts.dtype), axis=1
    )


def rows_at(ws: WindowState, ids: jax.Array) -> jax.Array:
    """``[K, n_buckets, n_channels]``: the whole rows of resources ``ids``.

    Readers pick their channel *after* this gather, on purpose. The TPU
    keeps a ``[R, B, E]`` window resource-minor and tiled; a gather of
    ``counts[ids, :, channel]`` wants another tiling and copies the whole
    window into it first, every call (the entry ``copy`` of ``flow.counts``
    in a decide step's trace, 74 us at 100k rows), while a gather of whole
    rows reads the window as it lies. Reads of several channels at the same
    ``ids`` share the one gather.

    A window of one channel (the occupy window, ``[R, 2B, 1]``) lies
    ``T(1,128)``, and no gather the v5e compiler has reads that as it lies:
    whole rows go through one copy of the window into row tiles (41 us at
    100k rows, the gather itself 2 us), a gather per bucket column through
    ``2B`` retiled columns and ``2B`` gathers (193 us), a dense masked sum
    with two vector gathers 68 us (measured alone, PERF.md section 6,
    PR 32). So it is read by whole rows too, and once a step for all of
    its readers (:func:`past_and_future_sums_at`)."""
    return ws.counts[ids]


def window_sum_at(
    spec: WindowSpec,
    ws: WindowState,
    now: jax.Array,
    channel: int,
    ids: jax.Array,
) -> jax.Array:
    """``[K]`` valid-bucket sums of one channel at resource rows ``ids``.

    Gather-first: reads ``O(K · n_buckets)`` instead of reducing the whole
    ``[n_resources, n_buckets]`` plane — the read path stays independent of
    the table size (matters at 10^5–10^6 rule slots)."""
    mask = valid_mask(spec, ws, now)
    rows = rows_at(ws, ids)[:, :, channel]  # [K, B]
    return jnp.sum(rows * mask[None, :].astype(rows.dtype), axis=1)


def window_sum_all(spec: WindowSpec, ws: WindowState, now: jax.Array) -> jax.Array:
    """``[n_resources, n_channels]`` sums over valid buckets."""
    mask = valid_mask(spec, ws, now)
    return jnp.sum(
        ws.counts * mask[None, :, None].astype(ws.counts.dtype), axis=1
    )


def avg_qps(spec: WindowSpec, total: jax.Array) -> jax.Array:
    """Per-second rate from a window sum (``StatisticNode.passQps`` divides by
    ``IntervalProperty.INTERVAL/1000``)."""
    return total.astype(jnp.float32) * (1000.0 / spec.interval_ms)


def shift_clock(clock: jax.Array, delta_ms: int) -> jax.Array:
    """An engine-ms array (a ring's ``starts``, a shaper or breaker clock)
    after the engine epoch moved forward by ``delta_ms``; ``NEVER`` stays
    ``NEVER``. The host's maintenance op, run well before int32 engine-ms
    wraps at ~24.8 days."""
    return jnp.where(clock == NEVER, clock, clock - jnp.int32(delta_ms))


# ---------------------------------------------------------------------------
# Future (occupy/borrow) windows — analog of FutureBucketLeapArray
# (``slots/statistic/metric/occupy/FutureBucketLeapArray.java``): same ring, but
# a slot is valid when its window lies strictly in the future within the next
# interval. Used by prioritized requests to "borrow" capacity from upcoming
# windows (``OccupiableBucketLeapArray.java:29-73``, ``StatisticNode.tryOccupyNext``).
# ---------------------------------------------------------------------------


def future_valid_mask(spec: WindowSpec, ws: WindowState, now: jax.Array) -> jax.Array:
    now = jnp.asarray(now, jnp.int32)
    ahead = ws.starts - now
    return (ahead > 0) & (ahead <= spec.interval_ms)


def future_sum(
    spec: WindowSpec, ws: WindowState, now: jax.Array, channel: int
) -> jax.Array:
    """``[n_resources]`` occupied counts waiting in future windows
    (``OccupiableBucketLeapArray.currentWaiting``)."""
    mask = future_valid_mask(spec, ws, now)
    return jnp.sum(
        ws.counts[:, :, channel] * mask[None, :].astype(ws.counts.dtype), axis=1
    )


def future_sum_at(
    spec: WindowSpec,
    ws: WindowState,
    now: jax.Array,
    channel: int,
    ids: jax.Array,
) -> jax.Array:
    """``[K]`` future-window sums at resource rows ``ids`` (gather-first
    counterpart of :func:`future_sum`)."""
    return past_and_future_sums_at(spec, ws, now, channel, ids)[1]


def past_and_future_sums_at(
    spec: WindowSpec,
    ws: WindowState,
    now: jax.Array,
    channel: int,
    ids: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """``([K], [K])``: :func:`window_sum_at` and :func:`future_sum_at` of one
    channel at resource rows ``ids``, from one fetch of the rows: the two
    are masks (the buckets behind ``now``, the buckets ahead of it) over the
    same ``[K, n_buckets]`` cells. The decide step reads the occupy window
    through here, once."""
    rows = rows_at(ws, ids)[:, :, channel]
    behind = valid_mask(spec, ws, now).astype(rows.dtype)
    ahead = future_valid_mask(spec, ws, now).astype(rows.dtype)
    return (
        jnp.sum(rows * behind[None, :], axis=1),
        jnp.sum(rows * ahead[None, :], axis=1),
    )


def add_future(
    spec: WindowSpec,
    ws: WindowState,
    now: jax.Array,
    wait_ms: jax.Array,
    resource_ids: jax.Array,
    channel: int,
    values: jax.Array,
    valid: Optional[jax.Array] = None,
    combine_desired=None,
) -> WindowState:
    """Scatter-add into the bucket ``wait_ms`` ahead of ``now`` (per request).

    reference: ``OccupiableBucketLeapArray.addWaiting(futureTime, n)``. Each
    request may target a different future slot, so the roll (which targeted
    slots are stale) is computed for the union of targeted slots first. One
    scatter-add then sorts the batch's values by target into a fresh sheet
    of deltas, ``B - 1`` lines of ``[R]`` (a scatter costs by the row, about
    9 ns on a v5e, so one serves all targets), and the counts are written
    one target bucket at a time, as :func:`_write_current` writes the
    current one: the slot's ``[R, E]`` slab is taken out of the ring, zeroed
    if its start is stale, its ``[R]`` column of the static ``channel``
    takes the target's line of deltas, and the slab is put back in place. A
    target no row aims at is skipped, so neither a scatter nor a reset ever
    takes the window as its operand (PERF.md section 5: the whole-window
    form was the top device op of a step whose shaping arms are live).

    The target window offset is clamped to ``[1, B-1]`` buckets ahead (``B``
    = ``spec.n_buckets``), so a row never lands on the current bucket. The
    ring is as long as ``ws`` was made, which may be longer than ``B``: a
    window that is also read back as *matured* (:func:`window_sum_at` over
    the ``B`` buckets behind ``now``) holds ``B`` live buckets behind and
    ``B - 1`` ahead, and in a ring of ``B`` slots the bucket ``k`` ahead
    shares its slot with the live bucket ``B - k`` behind, which the reset
    below would zero for every resource. ``engine.state.make_state`` gives
    the occupy window ``2 B`` slots for that reason. Rows with ``wait_ms <=
    0`` or ``valid=False`` are fully masked (they contribute neither counts
    nor slot resets).
    """
    now = jnp.asarray(now, jnp.int32)
    wait_ms = jnp.asarray(wait_ms, jnp.int32)
    row_ok = wait_ms > 0
    if valid is not None:
        row_ok = row_ok & valid
    values = jnp.where(row_ok, values, 0).astype(ws.counts.dtype)

    _, cur_start = bucket_index(spec, now)
    k = (now + wait_ms - cur_start) // spec.bucket_ms
    # masked rows aim at no target: they must not drive the reset union below
    k = jnp.where(row_ok, jnp.clip(k, 1, spec.n_buckets - 1), 0)
    ahead = jnp.arange(1, spec.n_buckets, dtype=jnp.int32)  # the targets
    tgt_start = cur_start + ahead * spec.bucket_ms
    tgt_slot = (tgt_start // spec.bucket_ms) % ws.starts.shape[0]
    aimed = jnp.any(k[:, None] == ahead[None, :], axis=0)

    # The start each targeted slot must hold (targets map to distinct slots:
    # there are fewer of them than slots in the ring).
    # `combine_desired` (e.g. a pmax over a mesh axis) lets sharded callers
    # agree on the reset union so the replicated `starts` vector cannot
    # diverge across devices when only the owner shard sees a borrow.
    desired = jnp.full_like(ws.starts, NEVER).at[tgt_slot].set(
        jnp.where(aimed, tgt_start, NEVER)
    )
    if combine_desired is not None:
        desired = combine_desired(desired)
    needs_reset = (desired != NEVER) & (desired != ws.starts)
    starts = jnp.where(needs_reset, desired, ws.starts)

    # every row's value into the line of its target; masked rows and ids past
    # the end go past the sheet's end and are dropped (a negative id counts
    # from the end, as `.at[]` has it)
    n_res = ws.counts.shape[0]
    res = jnp.where(resource_ids < 0, resource_ids + n_res, resource_ids)
    lands = (k > 0) & (res >= 0) & (res < n_res)
    sheet = jnp.zeros(((spec.n_buckets - 1) * n_res,), ws.counts.dtype)
    sheet = sheet.at[
        jnp.where(lands, (k - 1) * n_res + res, sheet.shape[0])
    ].add(values, mode="drop")

    counts = ws.counts
    for j in range(1, spec.n_buckets):
        slot = tgt_slot[j - 1]

        def write(c, slot=slot, j=j):
            slab = jax.lax.dynamic_index_in_dim(c, slot, axis=1, keepdims=False)
            slab = jnp.where(needs_reset[slot], jnp.zeros_like(slab), slab)
            slab = slab.at[:, int(channel)].add(sheet[(j - 1) * n_res : j * n_res])
            return jax.lax.dynamic_update_index_in_dim(c, slab, slot, axis=1)

        # a slot some shard aims at is written on every shard (its reset is
        # every shard's); one nobody aims at is left alone
        counts = jax.lax.cond(desired[slot] != NEVER, write, lambda c: c, counts)
    return WindowState(starts=starts, counts=counts)
