"""Device-resident circuit breaking: the breaker gate shared by both
decide backends.

The reference's ``DegradeSlot`` (``AbstractCircuitBreaker`` +
``ResponseTimeCircuitBreaker`` / ``ExceptionCircuitBreaker``) keeps one
CLOSED/OPEN/HALF_OPEN state machine per resource, fed by completion stats.
Here the whole machine is three ``[max_flows]`` state columns
(:class:`~sentinel_tpu.engine.state.BreakerState`) plus six rule columns
(``RuleTable.br_*``), and every transition is computed batch-vectorized
inside the decide step from the PR-16 outcome window — outcomes in,
breaker verdicts out, zero host round-trips.

Semantics, mapped to the reference:

- **CLOSED → OPEN** (``tryPass`` + the strategy's ``onRequestComplete``
  threshold test, evaluated lazily at decide time): over the fenced stat
  window, ``metric > threshold`` with ``total >= min_request_amount``,
  where metric is slow-ratio / error-ratio / error-count by strategy.
  Strict ``>`` like the reference.
- **OPEN → HALF_OPEN** (``retryTimeoutArrived`` + ``fromOpenToHalfOpen``):
  after ``recovery_timeout_ms``, the first in-range request of the flow in
  batch order wins the probe ticket (same-flow prefix rank 0 — batch-safe
  under fusion and shard_map, because the election happens in the one
  place that sees the whole batch in order) and proceeds through normal
  admission; every other row keeps answering DEGRADED.
- **HALF_OPEN → CLOSED / OPEN** (``fromHalfOpenToClose`` / the error
  rollback): decided by the probe's completion report inside the outcome
  step (:mod:`sentinel_tpu.engine.outcome`), not here — the decide path
  only re-arms a probe whose report never came (client died mid-probe)
  after another ``recovery_timeout_ms``.

The stats fence: ``opened_ms`` is stamped ``now`` on every transition and
the evaluation only reads outcome buckets whose start is at or after
``max(now - stat_interval_ms, opened_ms)`` — the device analog of the
reference's ``resetStat()`` on close, at bucket granularity, without
destroying the shared telemetry window.

The no-breaker cost is tiered. A table built with no degrade rules at
all carries ``None`` br_* columns — a structurally different jit pytree,
so that compile never traces the breaker arm and pays exactly zero. A
table WITH breakers gates everything behind one mesh-uniform ``lax.cond``
(any breaker row in this batch, psum-stitched OUTSIDE the cond), so
batches that touch no guarded flow pay one [N] gather + one psum and
nothing else — the ≤2% serve-path overhead contract.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sentinel_tpu.engine.config import EngineConfig
from sentinel_tpu.engine.rules import DegradeStrategy, RuleTable
from sentinel_tpu.engine.state import (
    BR_CLOSED,
    BR_HALF_OPEN,
    BR_OPEN,
    BreakerState,
    EngineState,
    OutcomeChannel,
)
from sentinel_tpu.stats.window import NEVER


# what the gate says it did (``breaker_gate``'s ``said``): whether it ran and
# the rows it had, known outside its cond, then what it counted inside
BR_COUNTED_FIELDS = 3  # rows shed, probe tickets given, flows tripped
BR_SAID_FIELDS = 2 + BR_COUNTED_FIELDS


def breaker_gate(
    config: EngineConfig,
    spec,
    state: EngineState,
    rules: RuleTable,
    now: jax.Array,  # int32 scalar
    safe_slot: jax.Array,  # int32 [N] clamped local slots
    active: jax.Array,  # bool [N] — ns-admitted owned rows
    flow_prefix,  # same-flow exclusive prefix closure over batch order
    psum,  # mesh reduction (identity single-shard)
) -> tuple:
    """Evaluate breaker transitions for one batch; returns
    ``(degraded, retry_ms, breaker', said)``.

    ``degraded`` rows must be stripped from ``active`` before admission
    (they write NO flow-window events, like namespace-guard refusals) and
    answer ``TokenStatus.DEGRADED`` with ``retry_ms`` in ``remaining``.
    All three outputs are local to the owner shard; the verdict psum
    stitches them exactly like the other owner-emitted statuses.

    ``said`` (``int32[BR_SAID_FIELDS]``, the same on every shard) is what
    the arm did, for the step's ``arms``: whether the gate ran, the rows on
    a guarded flow, the rows shed, the probe tickets given (each a move to
    HALF_OPEN, or a stale probe armed again) and the flows tripped to OPEN.
    """
    n = safe_slot.shape[0]
    if rules.br_strategy is None:
        # no degrade rules in this table: the None columns are part of the
        # jit pytree structure, so this compile carries no breaker arm at
        # all — the ≤2% overhead contract costs literally zero here
        return (
            jnp.zeros((n,), bool),
            jnp.zeros((n,), jnp.int32),
            state.breaker,
            jnp.zeros((BR_SAID_FIELDS,), jnp.int32),
        )
    f_local = rules.valid.shape[0]
    strat = rules.br_strategy[safe_slot].astype(jnp.int32)
    br_rows = active & (strat >= 0)
    # mesh-uniform predicate: the psum lives OUTSIDE the cond
    n_guarded = jnp.sum(psum(br_rows.astype(jnp.int32)))
    any_br = n_guarded > 0

    def gate_off(_):
        return (
            jnp.zeros((n,), bool),
            jnp.zeros((n,), jnp.int32),
            state.breaker,
            jnp.zeros((BR_COUNTED_FIELDS,), jnp.int32),
        )

    def gate_on(_):
        br = state.breaker
        st = br.state[safe_slot].astype(jnp.int32)
        opened = br.opened_ms[safe_slot]
        probe = br.probe_ms[safe_slot]
        thr = rules.br_threshold[safe_slot]
        minreq = rules.br_min_request[safe_slot]
        stat_ms = rules.br_stat_ms[safe_slot]
        rec_ms = rules.br_recovery_ms[safe_slot]

        # fenced stat window: buckets alive in the sliding window AND not
        # older than the stat interval or the last transition (opened_ms
        # doubles as the resetStat() fence; NEVER fences nothing)
        lo = jnp.maximum(now - stat_ms, opened)  # [N]
        starts = state.outcome.starts  # [B]
        age = now - starts
        bvalid = (age >= 0) & (age < spec.interval_ms)  # [B]
        inc = (bvalid[None, :] & (starts[None, :] >= lo[:, None])).astype(
            jnp.float32
        )  # [N, B]
        counts = state.outcome.counts[safe_slot]  # [N, B, C]
        total_i = jnp.sum(
            counts[:, :, int(OutcomeChannel.COMPLETE)]
            * inc.astype(counts.dtype),
            axis=1,
        )
        errs = jnp.sum(
            counts[:, :, int(OutcomeChannel.EXCEPTION)]
            * inc.astype(counts.dtype),
            axis=1,
        ).astype(jnp.float32)
        slows = jnp.sum(
            counts[:, :, int(OutcomeChannel.SLOW)]
            * inc.astype(counts.dtype),
            axis=1,
        ).astype(jnp.float32)
        denom = jnp.maximum(total_i.astype(jnp.float32), 1.0)
        metric = jnp.where(
            strat == int(DegradeStrategy.SLOW_REQUEST_RATIO),
            slows / denom,
            jnp.where(
                strat == int(DegradeStrategy.ERROR_RATIO),
                errs / denom,
                errs,
            ),
        )
        # strict > like the reference; gated on minRequestAmount
        crossing = (total_i >= minreq) & (metric > thr)

        is_closed = st == BR_CLOSED
        is_open = st == BR_OPEN
        is_half = st == BR_HALF_OPEN
        just_open = br_rows & is_closed & crossing
        open_elapsed = is_open & (now - opened >= rec_ms)
        probe_stale = is_half & (now - probe >= rec_ms)
        electable = br_rows & (open_elapsed | probe_stale)
        # HALF_OPEN probe election: first electable row of the flow in
        # batch order wins the ticket and proceeds through admission
        rank = flow_prefix(electable.astype(jnp.float32))
        is_probe = electable & (rank == 0.0)

        degraded = br_rows & (
            just_open
            | (is_open & ~open_elapsed)
            | (is_half & ~probe_stale)
            | (electable & ~is_probe)
        )
        retry = jnp.where(
            just_open | (electable & ~is_probe),
            rec_ms,
            jnp.where(
                is_open & ~open_elapsed,
                opened + rec_ms - now,
                probe + rec_ms - now,  # HALF_OPEN with a live probe
            ),
        )
        retry_ms = jnp.where(
            degraded, jnp.maximum(retry, 0), 0
        ).astype(jnp.int32)

        # transition scatters: values are flow-uniform (pure functions of
        # per-flow state + now), so duplicate same-flow rows write
        # identical values and .set stays deterministic; non-transition
        # rows route to row F which mode="drop" discards
        scat_open = jnp.where(just_open, safe_slot, f_local)
        scat_half = jnp.where(electable, safe_slot, f_local)
        br_state = (
            br.state.at[scat_open].set(jnp.int8(BR_OPEN), mode="drop")
            .at[scat_half].set(jnp.int8(BR_HALF_OPEN), mode="drop")
        )
        br_opened = br.opened_ms.at[scat_open].set(now, mode="drop")
        br_probe = (
            br.probe_ms.at[scat_open].set(jnp.int32(NEVER), mode="drop")
            .at[scat_half].set(now, mode="drop")
        )
        # this shard's rows shed, tickets given and flows tripped (the
        # first tripping row of a flow stands for it)
        tripped = just_open & (flow_prefix(just_open.astype(jnp.float32)) == 0.0)
        counted = jnp.stack([
            jnp.sum(degraded.astype(jnp.int32)),
            jnp.sum(is_probe.astype(jnp.int32)),
            jnp.sum(tripped.astype(jnp.int32)),
        ])
        return degraded, retry_ms, BreakerState(
            state=br_state, opened_ms=br_opened, probe_ms=br_probe
        ), counted

    degraded, retry_ms, breaker, counted = jax.lax.cond(
        any_br, gate_on, gate_off, None
    )
    # one three-entry collective, outside the cond like the predicate's
    said = jnp.concatenate([
        jnp.stack([any_br.astype(jnp.int32), n_guarded]), psum(counted)
    ])
    return degraded, retry_ms, breaker, said
