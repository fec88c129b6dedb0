"""Engine state: all mutable counters as one pytree of device arrays.

The analog of the reference's ``ClusterMetricStatistics`` registry of
per-flowId ``ClusterMetric`` LeapArrays (``metric/ClusterMetric.java:28-79``)
— flattened into ``[max_flows, n_buckets, events]`` tensors plus a
``[max_namespaces, n_buckets, 1]`` tensor for the namespace guard
(``GlobalRequestLimiter``).
"""

from __future__ import annotations

import enum
import typing
from typing import Callable, FrozenSet, NamedTuple, Optional

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


# -- the table of state columns ---------------------------------------------
# The three documents a leaf of the state can ride: the snapshot a standby
# boots from, the replication delta it is kept warm with, and the MOVE blob
# a namespace changes servers in.
SNAPSHOT, DELTA, MOVE = "snapshot", "delta", "move"
_ALL_DOCS = frozenset({SNAPSHOT, DELTA, MOVE})


class Column(NamedTuple):
    """One leaf of the device state, and all that the code around the step
    needs to know of it: the snapshot, delta and MOVE codecs
    (``cluster.state_codec``), their blob encoders (``ha.snapshot``,
    ``ha.replication``, ``cluster.rebalance``), the engine clock's re-base
    and the mesh placement (``parallel.sharding``) are loops over these
    entries and name no leaf themselves. What a leaf is when nothing has
    happened to it is not here: cold is what :func:`make_state` gives, and
    the importers start from that.

    ``key`` is what names a row durably, across slot assignments:
    ``"flow"`` a flow id (``RuleIndex.slot_of``), ``"namespace"`` a
    namespace's name (``RuleIndex.ns_of``), ``"param"`` a param rule's flow
    id (the service's ``_param_rules``), ``None`` a leaf without such rows
    (a ring's ``starts``), which is copied whole. On a mesh a leaf keyed by
    flow is sharded along the flow axis and every other one replicated.

    ``kind`` is how a value travels between two engine clocks.
    ``"window"``: the ``counts`` of a ring whose bucket clocks are the
    family's ``starts``; a MOVE ships the live window's sums and folds them
    into the destination's current bucket, and a delta zeroes the buckets
    whose start moved. ``"clock"``: int32 engine-ms with ``NEVER``; the
    re-base shifts it, a MOVE ships its distance from the source's now.
    ``"value"``: copied as it is.

    ``dirty`` is the service's dirty set whose slots ship the leaf's rows in
    a delta (a leaf keyed by namespace rides the rows its flows' slots
    feed); ``None`` for a leaf that a delta ships whole or not at all.
    ``wire`` is the short name the flat documents use (:attr:`delta_key`,
    :attr:`move_key`); a snapshot nests ``field`` under ``family``.
    ``docs`` is where the leaf rides, for those that do not ride all three
    (a MOVE blob is ring-free, so nothing unkeyed rides it)."""

    family: str
    field: str
    key: Optional[str]
    kind: str
    dirty: Optional[str]
    wire: str
    docs: FrozenSet[str] = _ALL_DOCS

    @property
    def name(self) -> str:
        return f"{self.family}.{self.field}"

    @property
    def delta_key(self) -> str:
        return f"{self.wire}_counts" if self.kind == "window" else self.wire

    @property
    def move_key(self) -> str:
        """One namespace moves at a time, so a leaf keyed by namespace
        ships its one row (``ns_sum``) and the others a row per id."""
        if self.kind == "window":
            return self.wire + ("_sum" if self.key == "namespace" else "_sums")
        return self.wire + "_rel" if self.kind == "clock" else self.wire


def _window(family: str, key: str, dirty: str) -> tuple:
    """A window's two leaves. Its ``starts`` go whole into snapshots and
    deltas; a MOVE blob is ring-free and has none."""
    return (
        Column(family, "starts", None, "clock", None, f"{family}_starts",
               frozenset({SNAPSHOT, DELTA})),
        Column(family, "counts", key, "window", dirty, family),
    )


STATE_COLUMNS = (
    *_window("flow", "flow", "flow"),
    *_window("occupy", "flow", "flow"),
    *_window("ns", "namespace", "flow"),
    Column("shaping", "lpt", "flow", "clock", "flow", "shaping_lpt"),
    Column("shaping", "warm_tokens", "flow", "value", "flow",
           "shaping_warm_tokens"),
    Column("shaping", "warm_filled", "flow", "clock", "flow",
           "shaping_warm_filled"),
    # completion reports dirty other slots, on another cadence, than
    # admission does: a set of its own keeps a delta from shipping a whole
    # flow row for every piggy-backed report
    *_window("outcome", "flow", "outcome"),
    # transitions happen only on rows that were batched or reported, so the
    # touched slots that carry a breaker are exactly the ones to ship
    Column("breaker", "state", "flow", "value", "breaker", "breaker_state"),
    Column("breaker", "opened_ms", "flow", "clock", "breaker",
           "breaker_opened"),
    Column("breaker", "probe_ms", "flow", "clock", "breaker",
           "breaker_probe"),
)


# family -> the NamedTuple its leaves live in
_FAMILY_TYPES = typing.get_type_hints(EngineState)


def leaf(state: "EngineState", column: Column):
    return getattr(getattr(state, column.family), column.field)


def state_of(leaf_of: Callable[[Column], object]) -> "EngineState":
    """The :class:`EngineState` whose leaf for each column is
    ``leaf_of(column)``."""
    return EngineState(**{
        family: _FAMILY_TYPES[family](**{
            c.field: leaf_of(c) for c in STATE_COLUMNS if c.family == family
        })
        for family in EngineState._fields
    })


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
