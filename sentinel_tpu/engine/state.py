"""Engine state: all mutable counters as one pytree of device arrays.

The analog of the reference's ``ClusterMetricStatistics`` registry of
per-flowId ``ClusterMetric`` LeapArrays (``metric/ClusterMetric.java:28-79``)
— flattened into ``[max_flows, n_buckets, events]`` tensors plus a
``[max_namespaces, n_buckets, 1]`` tensor for the namespace guard
(``GlobalRequestLimiter``).
"""

from __future__ import annotations

import enum
from typing import NamedTuple

import jax
import jax.numpy as jnp

from sentinel_tpu.engine.config import EngineConfig
from sentinel_tpu.stats.window import NEVER, WindowSpec, WindowState, make_window


class ClusterEvent(enum.IntEnum):
    """``ClusterFlowEvent`` (``ClusterMetricBucket``): PASS counts tokens,
    PASS_REQUEST counts RPCs (a request may acquire N tokens).

    ``LEASED`` (wire rev 5, no reference analog) counts tokens delegated to
    clients as short-TTL local-admission leases. A grant charges the full
    slice into the current bucket at grant time — the delegated tokens are
    *pre-paid*, so client-local admissions never touch the server and the
    device admission read (PASS + LEASED + matured borrows vs threshold)
    keeps the global limit without seeing them individually. Unused tokens
    are credited back (a negative fold) on renew/return when the charge
    bucket is provably still inside the live window; otherwise they simply
    expire with the window — the conservative direction. Because LEASED is
    an ordinary event column it rides psum'd mesh limits, snapshots,
    replication deltas, and MOVE window-sum handoffs unchanged."""

    PASS = 0
    PASS_REQUEST = 1
    BLOCK = 2
    BLOCK_REQUEST = 3
    OCCUPIED_PASS = 4
    LEASED = 5


N_CLUSTER_EVENTS = len(ClusterEvent)


class OutcomeChannel(enum.IntEnum):
    """Completion-outcome channels of the per-flow outcome window.

    The reference's ``MetricBucket`` records four event classes per bucket
    (pass/block/success/RT + exception, ``MetricBucket.java``); the admission
    half lives in :class:`ClusterEvent`, and these columns are the completion
    half, fed by the batched OUTCOME_REPORT wire op. ``RT_SUM`` accumulates
    milliseconds (int32 — reports are clamp-validated at the wire boundary so
    a bucket cannot overflow), ``COMPLETE`` / ``EXCEPTION`` count completions.
    Channels ``RT_HIST0 .. RT_HIST0 + N_RT_BUCKETS - 1`` are a coarse
    log2-bucketed RT histogram (SALSA-style compact cells, arXiv:2102.12531):
    a completion with RT ``r`` ms lands in bucket
    ``clip(floor(log2(r + 1)), 0, N_RT_BUCKETS - 1)``, so bucket ``j`` spans
    ``[2^j - 1, 2^(j+1) - 1)`` ms and the last bucket absorbs the tail. That
    is enough resolution for a device-side p99 read without per-flow sketch
    state."""

    RT_SUM = 0
    COMPLETE = 1
    EXCEPTION = 2
    # completions whose RT exceeded the flow's DegradeRule slow_rt_ms —
    # the SLOW_REQUEST_RATIO breaker numerator. Counted exactly at report
    # time (the per-flow cutoff is a rule column), not reconstructed from
    # the coarse log2 histogram, so the breaker ratio matches the
    # reference's per-request `rt > maxAllowedRt` test bit-for-bit.
    SLOW = 3
    RT_HIST0 = 4


# log2 RT histogram cells; bucket 11 spans [2047, inf) ms. Upper edges are
# 2^(j+1) - 1 ms (see OutcomeChannel docstring).
N_RT_BUCKETS = 12
N_OUTCOME_CHANNELS = int(OutcomeChannel.RT_HIST0) + N_RT_BUCKETS

# Upper edge (ms, inclusive-exclusive) of each RT histogram bucket; the last
# bucket is open-ended. Host-side p99 reads walk this table.
RT_BUCKET_UPPER_MS = tuple(
    (1 << (j + 1)) - 1 for j in range(N_RT_BUCKETS - 1)
) + (float("inf"),)


class ShapingState(NamedTuple):
    """Per-flow traffic-shaper clocks (the mutable halves of the reference's
    ``RateLimiterController.latestPassedTime`` and ``WarmUpController``'s
    ``storedTokens``/``lastFilledTime`` atomics, flattened to ``[max_flows]``
    columns). ``NEVER`` marks a slot whose shaper has not run yet: pacing
    starts unconstrained, warmup's first lazy sync sees a huge idle gap and
    fills the bucket to ``max_token`` — the cold state."""

    lpt: jax.Array  # int32 [F] — latest passed time (pacing), engine ms
    warm_tokens: jax.Array  # float32 [F] — warmup stored tokens
    warm_filled: jax.Array  # int32 [F] — last warmup sync second, engine ms


# circuit-breaker states (AbstractCircuitBreaker.State); plain ints so the
# kernel compares i8 columns without enum machinery
BR_CLOSED = 0
BR_OPEN = 1
BR_HALF_OPEN = 2


class BreakerState(NamedTuple):
    """Per-flow circuit-breaker columns (``AbstractCircuitBreaker``'s
    ``currentState`` + ``nextRetryTimestamp`` atomics, flattened to
    ``[max_flows]`` device columns so transitions run batch-vectorized
    inside the decide kernel).

    ``opened_ms`` doubles as the stats fence: every transition stamps it
    ``now``, and the breaker evaluation only reads outcome buckets whose
    start is >= ``max(now - stat_interval, opened_ms)`` — the device analog
    of the reference's ``resetStat()`` on close, without destroying the
    shared telemetry window. ``probe_ms`` is the HALF_OPEN probe ticket:
    the engine clock at which the current probe was elected (``NEVER``
    when no probe is in flight); a probe whose completion report never
    arrives re-arms after ``recovery_timeout_ms``."""

    state: jax.Array  # int8 [F] — BR_CLOSED / BR_OPEN / BR_HALF_OPEN
    opened_ms: jax.Array  # int32 [F] — last transition clock (stats fence)
    probe_ms: jax.Array  # int32 [F] — HALF_OPEN probe election clock


class EngineState(NamedTuple):
    flow: WindowState  # [F, B, E] current windows
    occupy: WindowState  # [F, 2B, 1] future (borrowed) windows: occupy_ring
    ns: WindowState  # [NS, B, 1] namespace request qps guard
    shaping: ShapingState  # [F] per-flow shaper clocks
    outcome: WindowState  # [F, B, N_OUTCOME_CHANNELS] completion outcomes
    breaker: BreakerState  # [F] per-flow circuit-breaker columns


def flow_spec(config: EngineConfig) -> WindowSpec:
    return WindowSpec(bucket_ms=config.bucket_ms, n_buckets=config.n_buckets)


def occupy_ring(config: EngineConfig) -> WindowSpec:
    """The ring the occupy window is made with: twice the flow window's.

    The occupy window is written up to ``n_buckets - 1`` buckets ahead
    (``add_future``: priority borrows, paced waits) and read back for a whole
    interval once a bucket has matured, so ``2 * n_buckets - 1`` of its
    buckets are live at a time. In a ring of ``n_buckets`` slots a booking
    ``k`` buckets ahead reset the matured bucket ``n_buckets - k`` behind,
    for every flow: booked tokens stopped counting up to ``n_buckets - 1``
    buckets early and their flow admitted that much over its count (PR 31).
    Reads keep :func:`flow_spec` (validity is by age); only the slot a start
    maps to follows the ring's length."""
    return WindowSpec(bucket_ms=config.bucket_ms,
                      n_buckets=2 * config.n_buckets)


def make_shaping(n_flows: int) -> ShapingState:
    return ShapingState(
        lpt=jnp.full((n_flows,), NEVER, dtype=jnp.int32),
        warm_tokens=jnp.zeros((n_flows,), dtype=jnp.float32),
        warm_filled=jnp.full((n_flows,), NEVER, dtype=jnp.int32),
    )


def make_breaker(n_flows: int) -> BreakerState:
    return BreakerState(
        state=jnp.zeros((n_flows,), dtype=jnp.int8),  # BR_CLOSED
        opened_ms=jnp.full((n_flows,), NEVER, dtype=jnp.int32),
        probe_ms=jnp.full((n_flows,), NEVER, dtype=jnp.int32),
    )


def make_state(config: EngineConfig) -> EngineState:
    spec = flow_spec(config)
    return EngineState(
        flow=make_window(spec, config.max_flows, N_CLUSTER_EVENTS),
        occupy=make_window(occupy_ring(config), config.max_flows, 1),
        ns=make_window(spec, config.max_namespaces, 1),
        shaping=make_shaping(config.max_flows),
        outcome=make_window(spec, config.max_flows, N_OUTCOME_CHANNELS),
        breaker=make_breaker(config.max_flows),
    )
