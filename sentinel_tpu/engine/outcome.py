"""Fused completion-outcome scatter: the device half of the outcome plane.

Clients report ``(flow, rt_ms, exception)`` completions in batches (the
OUTCOME_REPORT wire op, piggy-backed on request frames); the token service
funnels every decoded batch through the donated step built here. One step
performs a single window roll plus four scatter-adds into the per-flow
``state.outcome`` window ([F, B, N_OUTCOME_CHANNELS]):

- ``RT_SUM``     += rt_ms          (windowed RT accumulator, "Give Me Some
                                    Slack"-style sliding measurement)
- ``COMPLETE``   += 1
- ``EXCEPTION``  += exception
- ``RT_HIST0+b`` += 1 where ``b = clip(floor(log2(rt+1)), 0, NB-1)`` — the
  SALSA-style coarse log2 histogram cell for device-side p99.

The step is deliberately DECOUPLED from the admission kernel: completions
arrive on their own cadence (whenever a client's next frame carries a
piggy-backed report), and fusing them into ``decide`` would put a
data-dependent extra scatter on the serve path's critical step. Instead the
outcome step donates the full EngineState exactly like ``decide_donating`` —
the admission windows alias straight through, only ``outcome`` is rewritten —
so the serve path pays nothing while reporting is idle and the outcome path
reuses the same buffer-donation discipline.

Rows are pre-validated on the host (see ``TokenService.report_outcomes``:
negative / non-finite / oversized RTs are dropped and counted before they
reach the device); the kernel additionally masks ``valid=False`` rows by
routing them to an out-of-range resource id, which ``mode="drop"`` scatters
discard — padding rows cost nothing and can never poison a live slot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from sentinel_tpu.engine.config import EngineConfig, named
from sentinel_tpu.engine.prefix import segment_prefix_builder
from sentinel_tpu.engine.rules import DegradeStrategy
from sentinel_tpu.engine.state import (
    BR_CLOSED,
    BR_HALF_OPEN,
    BR_OPEN,
    BreakerState,
    EngineState,
    N_RT_BUCKETS,
    OutcomeChannel,
    flow_spec,
)
from sentinel_tpu.stats import window as W
from sentinel_tpu.stats.window import NEVER


def rt_bucket(rt_ms: jax.Array) -> jax.Array:
    """Log2 histogram cell for an RT in ms: ``clip(floor(log2(rt+1)), 0,
    NB-1)`` — computed with integer bit-length semantics (no float log), so
    the device and the scalar reference in tests agree bit-exactly."""
    r = jnp.maximum(jnp.asarray(rt_ms, jnp.int32), 0) + 1  # >= 1
    # floor(log2(r)) == (bit length of r) - 1; 31 - clz(r) without a clz
    # primitive: compare against the 31 powers of two reachable by int32.
    powers = jnp.asarray([1 << k for k in range(1, 31)], jnp.int32)
    blog = jnp.sum(r[:, None] >= powers[None, :], axis=1).astype(jnp.int32)
    return jnp.clip(blog, 0, N_RT_BUCKETS - 1)


def _resolve_probes(
    br: BreakerState,
    br_strategy: jax.Array,  # int8 [F] rule columns
    br_slow_rt_ms: jax.Array,  # int32 [F]
    gslot: jax.Array,  # int32 [K] clamped in-range slots
    in_rng: jax.Array,  # bool [K] valid & slot in range
    rt_ms: jax.Array,
    exc: jax.Array,
    now: jax.Array,
) -> tuple:
    """HALF_OPEN probe resolution — ``fromHalfOpenToClose`` / the error
    rollback: the FIRST report of each flow whose breaker sits HALF_OPEN
    with a live probe ticket decides the flow's fate. Success (fast for
    SLOW_REQUEST_RATIO, non-exception otherwise) → CLOSED with
    ``opened_ms = now`` (the stats fence excludes pre-recovery buckets,
    the device resetStat()); failure → straight back to OPEN with a fresh
    recovery clock. Any in-flight completion for the flow can resolve the
    probe, like the reference's ``onRequestComplete`` — the probe request
    is merely the only one the breaker ADMITTED."""
    f = br.state.shape[0]
    st = br.state[gslot].astype(jnp.int32)
    probe = br.probe_ms[gslot]
    live = in_rng & (st == BR_HALF_OPEN) & (probe != NEVER)

    zero = jnp.int32(0)

    def off(_):
        return br, zero, zero

    def on(_):
        # first live report per flow in batch order wins the resolution
        rank = segment_prefix_builder(gslot, "auto")(
            live.astype(jnp.float32)
        )
        elected = live & (rank == 0.0)
        strat = br_strategy[gslot].astype(jnp.int32)
        fail = jnp.where(
            strat == int(DegradeStrategy.SLOW_REQUEST_RATIO),
            jnp.asarray(rt_ms, jnp.int32) > br_slow_rt_ms[gslot],
            jnp.asarray(exc, jnp.int32) > 0,
        )
        new_st = jnp.where(fail, BR_OPEN, BR_CLOSED).astype(jnp.int8)
        scat = jnp.where(elected, gslot, f)
        reopened = jnp.sum((elected & fail).astype(jnp.int32))
        return BreakerState(
            state=br.state.at[scat].set(new_st, mode="drop"),
            opened_ms=br.opened_ms.at[scat].set(now, mode="drop"),
            probe_ms=br.probe_ms.at[scat].set(jnp.int32(NEVER), mode="drop"),
        ), jnp.sum(elected.astype(jnp.int32)) - reopened, reopened

    return jax.lax.cond(jnp.any(live), on, off, None)


def _outcome_core(
    config: EngineConfig,
    state: EngineState,
    slots: jax.Array,  # int32 [K] rule-slot ids (out-of-range = dropped)
    rt_ms: jax.Array,  # int32 [K] clamped response times
    exc: jax.Array,  # int32 [K] 1 = exception, 0 = success
    valid: jax.Array,  # bool [K]
    now: jax.Array,  # int32 engine ms
    br_strategy=None,  # int8 [F] rule column, or None (no breakers loaded)
    br_slow_rt_ms=None,  # int32 [F] rule column, or None
) -> tuple:
    """``(state', tally)``; ``tally`` is ``int32[TALLY_FIELDS]``: the
    breakers the reports resolved (``TALLY_*``)."""
    spec = flow_spec(config)
    k = slots.shape[0]
    # invalid rows scatter to row F, which mode="drop" discards entirely
    safe_slot = jnp.where(valid, slots, jnp.int32(config.max_flows))
    ones = jnp.ones((k,), jnp.int32)
    row_cols = [
        jnp.asarray(rt_ms, jnp.int32),
        ones,
        jnp.asarray(exc, jnp.int32),
    ]
    channels = (
        int(OutcomeChannel.RT_SUM),
        int(OutcomeChannel.COMPLETE),
        int(OutcomeChannel.EXCEPTION),
    )
    if br_strategy is not None:
        # SLOW channel: counted exactly at report time against the flow's
        # DegradeRule cutoff (rules without a breaker carry NO_SLOW_RT_MS,
        # so their rows never count) — the SLOW_REQUEST_RATIO numerator
        gslot = jnp.where(valid, slots, 0).astype(jnp.int32)
        in_rng = valid & (slots >= 0) & (slots < br_strategy.shape[0])
        is_slow = (
            jnp.asarray(rt_ms, jnp.int32) > br_slow_rt_ms[gslot]
        ).astype(jnp.int32)
        row_cols.append(is_slow)
        channels = channels + (int(OutcomeChannel.SLOW),)
    rows = jnp.stack(row_cols, axis=1)
    ws = W.add_event_rows(
        spec, state.outcome, now, safe_slot, rows, channels=channels
    )
    # histogram cell: one extra scatter with a traced channel id (the roll
    # inside add_events is a no-op — the slot was refreshed just above)
    ws = W.add_events(
        spec, ws, now,
        resource_ids=safe_slot,
        channel_ids=int(OutcomeChannel.RT_HIST0) + rt_bucket(rt_ms),
        values=ones,
    )
    breaker, closed, reopened = state.breaker, jnp.int32(0), jnp.int32(0)
    if br_strategy is not None:
        breaker, closed, reopened = _resolve_probes(
            state.breaker, br_strategy, br_slow_rt_ms, gslot, in_rng,
            rt_ms, exc, now,
        )
    tally = jnp.stack([closed, reopened]).astype(jnp.int32)
    return state._replace(outcome=ws, breaker=breaker), tally


# what a tallying step says it did, ``int32[TALLY_FIELDS]``: HALF_OPEN
# breakers a report closed, HALF_OPEN breakers a report reopened
TALLY_CLOSED, TALLY_REOPENED = 0, 1
TALLY_FIELDS = 2


def outcome_step_donating(config: EngineConfig, tally: bool = False):
    """Build the jitted donated step ``(state, slots, rt, exc, valid, now)
    -> state'``, or with ``tally`` ``-> (state', int32[TALLY_FIELDS])``: the
    serving form, whose few bytes tell the host which breakers the step
    resolved, without a read of the state. The full EngineState is donated
    (the admission windows
    alias through untouched), mirroring ``decide_donating``'s contract:
    the caller's lock must make the passed state the only live reference.

    When breakers are loaded the caller additionally passes the
    ``br_strategy``/``br_slow_rt_ms`` rule columns, which turns on the
    SLOW-channel scatter and HALF_OPEN probe resolution (a separate jit
    trace; the 6-arg form stays bit-identical to the pre-breaker step)."""
    def step(state, slots, rt, exc, valid, now, *br):
        out = _outcome_core(config, state, slots, rt, exc, valid, now, *br)
        return out if tally else out[0]

    return jax.jit(named(step, "outcome_step"), donate_argnums=(0,))
