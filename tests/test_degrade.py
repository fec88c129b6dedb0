"""Circuit-breaker parity: the rule-strategy tensor columns in
``_decide_core`` / the outcome step against a scalar reference port.

The scalar port below mirrors ``engine/degrade.breaker_gate`` and
``engine/outcome._resolve_probes`` op for op — the fenced stat window at
bucket granularity, the strict-``>`` threshold gated on
``min_request_amount``, the per-flow HALF_OPEN probe election by batch
order, OPEN retry-after arithmetic, and probe resolution by the FIRST
completion report — in ``np.float32`` metric arithmetic, so every parity
assertion is exact equality (state bytes, verdict codes, clock stamps),
not a tolerance band. The same seeded mixed-strategy stream then runs
through ``decide_fused_donating`` and the 8-virtual-device
``make_sharded_decide`` step, which must stay bit-identical: the probe
election is the one place that sees the whole batch in order, so fusion
and shard_map must not change who wins the ticket.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.engine import (
    ClusterFlowRule,
    DegradeRule,
    DegradeStrategy,
    EngineConfig,
    TokenStatus,
    build_rule_table,
    decide,
    make_batch,
    make_state,
)
from sentinel_tpu.engine.decide import (
    decide_fused_donating,
    pack_batch,
    unpack_verdicts,
)
from sentinel_tpu.engine.outcome import outcome_step_donating
from sentinel_tpu.engine.state import (
    BR_CLOSED,
    BR_HALF_OPEN,
    BR_OPEN,
    flow_spec,
)
from sentinel_tpu.stats import window as W

f32 = np.float32
NEVER = int(W.NEVER)
SLOW = DegradeStrategy.SLOW_REQUEST_RATIO
ERR_RATIO = DegradeStrategy.ERROR_RATIO
ERR_COUNT = DegradeStrategy.ERROR_COUNT
DEG = int(TokenStatus.DEGRADED)

# max_flows divides the 8-device mesh evenly (4 slots per shard) and the
# 24-flow fixture spans 6 shards, so the sharded run exercises real
# cross-shard breaker rows, not a single owner shard
CFG = EngineConfig(max_flows=32, max_namespaces=4, batch_size=64)


# ---------------------------------------------------------------------------
# scalar reference port
# ---------------------------------------------------------------------------
class ScalarBreaker:
    """Scalar mirror of the breaker plane: rule columns, the three state
    columns, and the outcome window's COMPLETE/EXCEPTION/SLOW channels
    (shared starts ring, mask-on-read, zero-on-rewrite — exactly
    ``stats/window.py``)."""

    def __init__(self, config, table):
        t = jax.device_get(table)
        self.spec = flow_spec(config)
        F, B = config.max_flows, self.spec.n_buckets
        self.valid = np.asarray(t.valid)
        self.strategy = np.asarray(t.br_strategy, np.int64)
        self.thr = np.asarray(t.br_threshold, f32)
        self.slow_rt = np.asarray(t.br_slow_rt_ms, np.int64)
        self.minreq = np.asarray(t.br_min_request, np.int64)
        self.stat_ms = np.asarray(t.br_stat_ms, np.int64)
        self.rec_ms = np.asarray(t.br_recovery_ms, np.int64)
        self.state = np.zeros(F, np.int64)
        self.opened = np.full(F, NEVER, np.int64)
        self.probe = np.full(F, NEVER, np.int64)
        self.starts = np.full(B, NEVER, np.int64)
        self.counts = np.zeros((F, B, 3), np.int64)  # COMPLETE, EXC, SLOW

    # -- outcome window -----------------------------------------------------
    def _roll(self, now):
        idx = (now // self.spec.bucket_ms) % self.spec.n_buckets
        cur = now - now % self.spec.bucket_ms
        if self.starts[idx] != cur:
            self.counts[:, idx, :] = 0
            self.starts[idx] = cur
        return idx

    def report(self, now, rows):
        """``rows``: [(slot, rt_ms, exc)] — one OUTCOME_REPORT batch.

        Probe resolution reads the PRE-step breaker state (the device
        gathers before it scatters): the first live report of each
        HALF_OPEN-with-ticket flow decides the flow's fate.
        """
        resolved = set()
        for s, rt, exc in rows:
            if s in resolved:
                continue
            if self.state[s] == BR_HALF_OPEN and self.probe[s] != NEVER:
                fail = (
                    rt > self.slow_rt[s]
                    if self.strategy[s] == int(SLOW)
                    else exc > 0
                )
                self.state[s] = BR_OPEN if fail else BR_CLOSED
                self.opened[s] = now
                self.probe[s] = NEVER
                resolved.add(s)
        idx = self._roll(now)
        for s, rt, exc in rows:
            self.counts[s, idx, 0] += 1
            self.counts[s, idx, 1] += int(exc)
            self.counts[s, idx, 2] += int(rt > self.slow_rt[s])

    def _fenced(self, now, s):
        lo = max(now - self.stat_ms[s], self.opened[s])
        age = now - self.starts
        m = (age >= 0) & (age < self.spec.interval_ms) & (self.starts >= lo)
        c = self.counts[s][m]
        return int(c[:, 0].sum()), int(c[:, 1].sum()), int(c[:, 2].sum())

    # -- the breaker gate ---------------------------------------------------
    def decide(self, now, slots):
        """One batch of valid rows; returns ``(degraded, retry_ms)`` and
        applies the transition scatters, mirroring ``breaker_gate``."""
        n = len(slots)
        s = np.asarray(slots, np.int64)
        br_rows = self.valid[s] & (self.strategy[s] >= 0)
        st, opened, probe = self.state[s], self.opened[s], self.probe[s]
        rec = self.rec_ms[s]

        crossing = np.zeros(n, bool)
        for i in range(n):
            if not br_rows[i]:
                continue
            total, errs, slows = self._fenced(now, s[i])
            denom = f32(max(float(total), 1.0))
            if self.strategy[s[i]] == int(SLOW):
                metric = f32(f32(slows) / denom)
            elif self.strategy[s[i]] == int(ERR_RATIO):
                metric = f32(f32(errs) / denom)
            else:
                metric = f32(errs)
            crossing[i] = total >= self.minreq[s[i]] and metric > self.thr[s[i]]

        is_closed = st == BR_CLOSED
        is_open = st == BR_OPEN
        is_half = st == BR_HALF_OPEN
        just_open = br_rows & is_closed & crossing
        open_elapsed = is_open & (now - opened >= rec)
        probe_stale = is_half & (now - probe >= rec)
        electable = br_rows & (open_elapsed | probe_stale)
        seen = set()
        is_probe = np.zeros(n, bool)
        for i in range(n):
            if electable[i] and int(s[i]) not in seen:
                is_probe[i] = True
                seen.add(int(s[i]))

        degraded = br_rows & (
            just_open
            | (is_open & ~open_elapsed)
            | (is_half & ~probe_stale)
            | (electable & ~is_probe)
        )
        retry = np.where(
            just_open | (electable & ~is_probe),
            rec,
            np.where(is_open & ~open_elapsed,
                     opened + rec - now, probe + rec - now),
        )
        retry = np.where(degraded, np.maximum(retry, 0), 0)

        for i in range(n):
            if just_open[i]:
                self.state[s[i]] = BR_OPEN
                self.opened[s[i]] = now
                self.probe[s[i]] = NEVER
        for i in range(n):
            if electable[i]:
                self.state[s[i]] = BR_HALF_OPEN
                self.probe[s[i]] = now
        return degraded, retry

    def assert_matches(self, state):
        np.testing.assert_array_equal(
            np.asarray(state.breaker.state), self.state.astype(np.int8)
        )
        np.testing.assert_array_equal(
            np.asarray(state.breaker.opened_ms),
            self.opened.astype(np.int32),
        )
        np.testing.assert_array_equal(
            np.asarray(state.breaker.probe_ms), self.probe.astype(np.int32)
        )


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------
def _mixed_rules():
    """24 flows across 6 shard slabs: plain every 4th, the three
    strategies cycling on the rest, with knobs varied enough that trips,
    recoveries, and stale probes all occur on the seeded stream."""
    flow_rules, degrade_rules = [], []
    for fid in range(1, 25):
        flow_rules.append(
            ClusterFlowRule(flow_id=fid, count=1e9, namespace="ns0")
        )
        if fid % 4 == 0:
            continue  # unguarded flow: the gate must never touch it
        strat = DegradeStrategy(fid % 3)
        degrade_rules.append(DegradeRule(
            fid, strat,
            threshold=4.0 if strat == ERR_COUNT else 0.2 + 0.1 * (fid % 3),
            slow_rt_ms=20 + fid,
            min_request_amount=3 + fid % 4,
            stat_interval_ms=400 + 100 * (fid % 5),
            recovery_timeout_ms=250 + 50 * (fid % 4),
            namespace="ns0",
        ))
    return flow_rules, degrade_rules


def _build(cfg=CFG):
    flow_rules, degrade_rules = _mixed_rules()
    table, index = build_rule_table(
        cfg, flow_rules, ns_max_qps=1e9, degrade_rules=degrade_rules
    )
    return table, index


def _decide_rows(cfg, state, table, now, slots):
    batch = make_batch(cfg, slots, [1] * len(slots), [False] * len(slots))
    state, v = decide(cfg, state, table, batch, jnp.int32(now))
    n = len(slots)
    return state, (
        np.asarray(v.status)[:n].astype(np.int64),
        np.asarray(v.remaining)[:n].astype(np.int64),
    )


def _stream(seed, rounds, slots_pool, rng_rt=60):
    """Seeded script of (kind, now, rows) events: interleaved reports and
    decide batches with irregular clock advances and occasional report
    droughts (probe-stale coverage)."""
    rng = np.random.default_rng(seed)
    now = 10_000
    script = []
    for _ in range(rounds):
        now += int(rng.integers(37, 211))
        if rng.random() < 0.45:
            # bursts concentrate on a few focus flows so per-window counts
            # actually clear min_request_amount — a uniform spray over 24
            # flows would leave every stat window below the gate
            focus = rng.choice(slots_pool, size=3, replace=False)
            k = int(rng.integers(18, 40))
            rows = [
                (int(rng.choice(focus)),
                 int(rng.integers(0, rng_rt)),
                 int(rng.random() < 0.45))
                for _ in range(k)
            ]
            script.append(("report", now, rows))
        else:
            k = int(rng.integers(8, 25))
            script.append((
                "decide", now,
                [int(rng.choice(slots_pool)) for _ in range(k)],
            ))
    return script


def _assert_verdicts(status, remaining, degraded, retry):
    want = np.where(
        degraded, int(TokenStatus.DEGRADED), int(TokenStatus.OK)
    )
    np.testing.assert_array_equal(status, want)
    np.testing.assert_array_equal(remaining[degraded], retry[degraded])


# ---------------------------------------------------------------------------
# seeded mixed-strategy stream: exact state + verdict + clock parity
# ---------------------------------------------------------------------------
class TestScalarParity:
    @pytest.mark.slow
    @pytest.mark.parametrize("seed", [0xB41, 0xB42, 0xB43])
    def test_stream_state_verdict_clock_exact(self, seed):
        table, index = _build()
        state = make_state(CFG)
        ostep = outcome_step_donating(CFG)
        ref = ScalarBreaker(CFG, table)
        slots_pool = [index.lookup(f) for f in range(1, 25)]
        trips = probes = 0
        for kind, now, rows in _stream(seed, rounds=90,
                                       slots_pool=slots_pool):
            if kind == "report":
                k = len(rows)
                state = ostep(
                    state,
                    jnp.asarray([r[0] for r in rows], jnp.int32),
                    jnp.asarray([r[1] for r in rows], jnp.int32),
                    jnp.asarray([r[2] for r in rows], jnp.int32),
                    jnp.ones((k,), bool),
                    jnp.int32(now),
                    table.br_strategy,
                    table.br_slow_rt_ms,
                )
                ref.report(now, rows)
            else:
                prev_open = (ref.state == BR_OPEN).sum()
                state, (status, remaining) = _decide_rows(
                    CFG, state, table, now, rows
                )
                degraded, retry = ref.decide(now, rows)
                _assert_verdicts(status, remaining, degraded, retry)
                trips += int((ref.state == BR_OPEN).sum() > prev_open)
                probes += int((ref.state == BR_HALF_OPEN).sum() > 0)
            ref.assert_matches(state)
        # the stream actually exercised the machine — a parity pass over
        # an idle breaker would prove nothing
        assert trips >= 3
        assert probes >= 3

    def test_unguarded_flows_never_touched(self):
        table, index = _build()
        state = make_state(CFG)
        ostep = outcome_step_donating(CFG)
        s = index.lookup(4)  # fid % 4 == 0: no DegradeRule
        state = ostep(
            state, jnp.asarray([s] * 8, jnp.int32),
            jnp.full((8,), 10_000, jnp.int32),  # absurd RTs, all failing
            jnp.ones((8,), jnp.int32), jnp.ones((8,), bool),
            jnp.int32(10_000), table.br_strategy, table.br_slow_rt_ms,
        )
        state, (status, _) = _decide_rows(
            CFG, state, table, 10_050, [s] * 6
        )
        assert (status == int(TokenStatus.OK)).all()
        assert int(np.asarray(state.breaker.state)[s]) == BR_CLOSED


# ---------------------------------------------------------------------------
# per-strategy threshold semantics (strict >, minRequestAmount gate)
# ---------------------------------------------------------------------------
class TestStrategyThresholds:
    def _one(self, strategy, threshold, slow_rt=20, minreq=10):
        cfg = EngineConfig(max_flows=8, max_namespaces=2, batch_size=16)
        table, index = build_rule_table(
            cfg, [ClusterFlowRule(flow_id=1, count=1e9)], ns_max_qps=1e9,
            degrade_rules=[DegradeRule(
                1, strategy, threshold=threshold, slow_rt_ms=slow_rt,
                min_request_amount=minreq, stat_interval_ms=1000,
                recovery_timeout_ms=5000,
            )],
        )
        return cfg, table, index.lookup(1)

    def _pump(self, cfg, table, s, rt_exc_pairs, now=1000):
        state = make_state(cfg)
        ostep = outcome_step_donating(cfg)
        k = len(rt_exc_pairs)
        state = ostep(
            state, jnp.full((k,), s, jnp.int32),
            jnp.asarray([p[0] for p in rt_exc_pairs], jnp.int32),
            jnp.asarray([p[1] for p in rt_exc_pairs], jnp.int32),
            jnp.ones((k,), bool), jnp.int32(now),
            table.br_strategy, table.br_slow_rt_ms,
        )
        state, (status, _) = _decide_rows(cfg, state, table, now + 50, [s])
        return int(status[0]), int(np.asarray(state.breaker.state)[s])

    def test_slow_ratio_trips_strictly_above(self):
        cfg, table, s = self._one(SLOW, threshold=0.5, slow_rt=20, minreq=10)
        # 5/10 slow == threshold exactly: strict > must NOT trip
        even = [(100, 0)] * 5 + [(1, 0)] * 5
        assert self._pump(cfg, table, s, even) == (
            int(TokenStatus.OK), BR_CLOSED)
        # 6/10 slow: trips (and the cutoff itself is strict too: rt == 20
        # is NOT slow)
        over = [(100, 0)] * 6 + [(20, 0)] * 4
        assert self._pump(cfg, table, s, over) == (
            int(TokenStatus.DEGRADED), BR_OPEN)

    def test_error_ratio_gated_on_min_request(self):
        cfg, table, s = self._one(ERR_RATIO, threshold=0.25, minreq=10)
        # 9 completions at 100% errors: below minRequestAmount, no trip
        assert self._pump(cfg, table, s, [(5, 1)] * 9) == (
            int(TokenStatus.OK), BR_CLOSED)
        # the 10th arrives: trips
        assert self._pump(cfg, table, s, [(5, 1)] * 10) == (
            int(TokenStatus.DEGRADED), BR_OPEN)

    def test_error_count_is_a_raw_count(self):
        cfg, table, s = self._one(ERR_COUNT, threshold=4.0, minreq=1)
        assert self._pump(cfg, table, s, [(5, 1)] * 4 + [(5, 0)] * 20) == (
            int(TokenStatus.OK), BR_CLOSED)
        assert self._pump(cfg, table, s, [(5, 1)] * 5) == (
            int(TokenStatus.DEGRADED), BR_OPEN)


# ---------------------------------------------------------------------------
# HALF_OPEN lifecycle: election, resolution, stale re-arm
# ---------------------------------------------------------------------------
class TestProbeLifecycle:
    def _tripped(self):
        cfg = EngineConfig(max_flows=8, max_namespaces=2, batch_size=32)
        table, index = build_rule_table(
            cfg, [ClusterFlowRule(flow_id=1, count=1e9)], ns_max_qps=1e9,
            degrade_rules=[DegradeRule(
                1, ERR_RATIO, threshold=0.2, min_request_amount=5,
                stat_interval_ms=1000, recovery_timeout_ms=300,
            )],
        )
        s = index.lookup(1)
        state = make_state(cfg)
        ostep = outcome_step_donating(cfg)
        state = ostep(
            state, jnp.full((8,), s, jnp.int32),
            jnp.full((8,), 5, jnp.int32), jnp.ones((8,), jnp.int32),
            jnp.ones((8,), bool), jnp.int32(1000),
            table.br_strategy, table.br_slow_rt_ms,
        )
        state, (status, _) = _decide_rows(cfg, state, table, 1050, [s])
        assert status[0] == int(TokenStatus.DEGRADED)
        return cfg, table, s, state, ostep

    def test_open_answers_retry_after_countdown(self):
        cfg, table, s, state, _ = self._tripped()
        state, (status, remaining) = _decide_rows(
            cfg, state, table, 1150, [s]
        )
        assert status[0] == int(TokenStatus.DEGRADED)
        # opened at 1050, recovery 300 → 200ms left at now=1150
        assert remaining[0] == 200

    def test_single_probe_in_one_batch(self):
        cfg, table, s, state, _ = self._tripped()
        state, (status, _) = _decide_rows(
            cfg, state, table, 1400, [s] * 12
        )
        assert int((status == int(TokenStatus.OK)).sum()) == 1
        assert status[0] == int(TokenStatus.OK)  # first row wins the ticket
        assert int((status == int(TokenStatus.DEGRADED)).sum()) == 11
        assert int(np.asarray(state.breaker.state)[s]) == BR_HALF_OPEN

    def test_probe_success_closes_and_fences_stats(self):
        cfg, table, s, state, ostep = self._tripped()
        state, _ = _decide_rows(cfg, state, table, 1400, [s])  # elect
        state = ostep(
            state, jnp.asarray([s], jnp.int32), jnp.asarray([5], jnp.int32),
            jnp.asarray([0], jnp.int32), jnp.ones((1,), bool),
            jnp.int32(1450), table.br_strategy, table.br_slow_rt_ms,
        )
        assert int(np.asarray(state.breaker.state)[s]) == BR_CLOSED
        # opened_ms = resolution time: the fence excludes the pre-recovery
        # error buckets, so the healed flow serves instead of re-tripping
        assert int(np.asarray(state.breaker.opened_ms)[s]) == 1450
        state, (status, _) = _decide_rows(cfg, state, table, 1500, [s] * 4)
        assert (status == int(TokenStatus.OK)).all()

    def test_probe_failure_reopens_with_fresh_clock(self):
        cfg, table, s, state, ostep = self._tripped()
        state, _ = _decide_rows(cfg, state, table, 1400, [s])
        state = ostep(
            state, jnp.asarray([s], jnp.int32), jnp.asarray([5], jnp.int32),
            jnp.asarray([1], jnp.int32), jnp.ones((1,), bool),
            jnp.int32(1450), table.br_strategy, table.br_slow_rt_ms,
        )
        assert int(np.asarray(state.breaker.state)[s]) == BR_OPEN
        assert int(np.asarray(state.breaker.opened_ms)[s]) == 1450
        state, (status, remaining) = _decide_rows(
            cfg, state, table, 1500, [s]
        )
        assert status[0] == int(TokenStatus.DEGRADED)
        assert remaining[0] == 250  # 1450 + 300 - 1500

    def test_stale_probe_rearms_after_recovery_timeout(self):
        # the probe's report never arrives (client died mid-probe): after
        # another recovery_timeout the NEXT request takes over the ticket
        cfg, table, s, state, _ = self._tripped()
        state, _ = _decide_rows(cfg, state, table, 1400, [s])
        state, (status, _) = _decide_rows(cfg, state, table, 1500, [s])
        assert status[0] == int(TokenStatus.DEGRADED)  # ticket still live
        state, (status, _) = _decide_rows(cfg, state, table, 1750, [s])
        assert status[0] == int(TokenStatus.OK)  # re-armed at 1400+300
        assert int(np.asarray(state.breaker.probe_ms)[s]) == 1750


# ---------------------------------------------------------------------------
# fused + sharded bit-identity
# ---------------------------------------------------------------------------
def _stack_batches(cfg, frames):
    batches = [
        make_batch(cfg, rows, [1] * len(rows), [False] * len(rows))
        for rows in frames
    ]
    return jax.tree.map(lambda *ls: jnp.stack(ls), *batches)


def _prepared(cfg, table, index, seed):
    """Replay a fixed report/decide prefix so independent state copies are
    bit-identical before the variant under test runs."""
    state = make_state(cfg)
    ostep = outcome_step_donating(cfg)
    slots_pool = [index.lookup(f) for f in range(1, 25)]
    for kind, now, rows in _stream(seed, rounds=30, slots_pool=slots_pool):
        if kind == "report":
            k = len(rows)
            state = ostep(
                state, jnp.asarray([r[0] for r in rows], jnp.int32),
                jnp.asarray([r[1] for r in rows], jnp.int32),
                jnp.asarray([r[2] for r in rows], jnp.int32),
                jnp.ones((k,), bool), jnp.int32(now),
                table.br_strategy, table.br_slow_rt_ms,
            )
        else:
            state, _ = _decide_rows(cfg, state, table, now, rows)
    return state


class TestFusedParity:
    def test_fused_burst_elects_exactly_one_probe(self):
        """Three stacked frames of one OPEN-past-recovery flow share one
        ``now``: frame 0 elects the probe, frames 1-2 must see the live
        ticket and keep answering DEGRADED — exactly one admit in 3×N."""
        cfg = EngineConfig(max_flows=8, max_namespaces=2, batch_size=16)
        table, index = build_rule_table(
            cfg, [ClusterFlowRule(flow_id=1, count=1e9)], ns_max_qps=1e9,
            degrade_rules=[DegradeRule(
                1, ERR_RATIO, threshold=0.2, min_request_amount=5,
                stat_interval_ms=1000, recovery_timeout_ms=300,
            )],
        )
        s = index.lookup(1)
        state = make_state(cfg)
        ostep = outcome_step_donating(cfg)
        state = ostep(
            state, jnp.full((8,), s, jnp.int32),
            jnp.full((8,), 5, jnp.int32), jnp.ones((8,), jnp.int32),
            jnp.ones((8,), bool), jnp.int32(1000),
            table.br_strategy, table.br_slow_rt_ms,
        )
        state, _ = _decide_rows(cfg, state, table, 1050, [s])  # trip
        fused = decide_fused_donating(cfg, depth=3)
        batches = _stack_batches(cfg, [[s] * 16] * 3)
        state, v = fused(state, table, pack_batch(batches, 1400))
        status = unpack_verdicts(v).status[:, :16]
        assert int((status == int(TokenStatus.OK)).sum()) == 1
        assert status[0, 0] == int(TokenStatus.OK)
        assert int((status == int(TokenStatus.DEGRADED)).sum()) == 47

    @pytest.mark.slow
    @pytest.mark.parametrize("depth", [2, 4])
    def test_fused_bit_identical_to_sequential(self, depth):
        table, index = _build()
        rng = np.random.default_rng(0xF00D + depth)
        slots_pool = [index.lookup(f) for f in range(1, 25)]
        frames = [
            [int(rng.choice(slots_pool)) for _ in range(CFG.batch_size)]
            for _ in range(depth)
        ]
        now = 14_000

        seq_state = _prepared(CFG, table, index, seed=0xABC)
        seq_v = []
        for rows in frames:
            seq_state, v = _decide_rows(CFG, seq_state, table, now, rows)
            seq_v.append(v)

        fused_state = _prepared(CFG, table, index, seed=0xABC)
        fused = decide_fused_donating(CFG, depth=depth)
        fused_state, fv = fused(
            fused_state, table, pack_batch(_stack_batches(CFG, frames), now)
        )
        fv = unpack_verdicts(fv)
        for k in range(depth):
            np.testing.assert_array_equal(
                np.asarray(fv.status)[k, : CFG.batch_size], seq_v[k][0]
            )
            np.testing.assert_array_equal(
                np.asarray(fv.remaining)[k, : CFG.batch_size], seq_v[k][1]
            )
        for leaf_a, leaf_b in zip(seq_state.breaker, fused_state.breaker):
            np.testing.assert_array_equal(
                np.asarray(leaf_a), np.asarray(leaf_b)
            )


class TestShardedParity:
    @pytest.fixture
    def mesh(self):
        from sentinel_tpu.parallel.sharding import make_flow_mesh

        assert len(jax.devices()) == 8, "conftest provides 8 virtual devices"
        return make_flow_mesh()

    @pytest.mark.slow
    def test_sharded_decide_bit_identical(self, mesh):
        """The same mixed-strategy stream decided on the 8-device mesh:
        per-round verdicts AND the breaker columns must match the
        single-shard run bit for bit (the probe election and transition
        scatters happen on the owner shard; psum stitches the verdicts)."""
        from sentinel_tpu.parallel.sharding import (
            make_sharded_decide,
            shard_rules,
            shard_state,
        )

        table, index = _build()
        sharded_step = make_sharded_decide(CFG, mesh)
        table_8 = shard_rules(table, mesh)
        state = _prepared(CFG, table, index, seed=0xD15C)
        rng = np.random.default_rng(0xD15C)
        slots_pool = [index.lookup(f) for f in range(1, 25)]
        now = 14_000
        for _ in range(6):
            now += int(rng.integers(80, 400))
            rows = [
                int(rng.choice(slots_pool)) for _ in range(CFG.batch_size)
            ]
            batch = make_batch(CFG, rows, [1] * len(rows),
                               [False] * len(rows))
            state_8 = shard_state(state, mesh)
            out_8, v8 = sharded_step(state_8, table_8, batch, jnp.int32(now))
            state, v1 = decide(CFG, state, table, batch, jnp.int32(now))
            np.testing.assert_array_equal(
                np.asarray(v8.status), np.asarray(v1.status)
            )
            np.testing.assert_array_equal(
                np.asarray(v8.remaining), np.asarray(v1.remaining)
            )
            for leaf_a, leaf_b in zip(out_8.breaker, state.breaker):
                np.testing.assert_array_equal(
                    np.asarray(leaf_a), np.asarray(leaf_b)
                )
        # the mesh rounds actually saw breaker traffic
        assert int((np.asarray(state.breaker.state) != BR_CLOSED).sum()) > 0


# ---------------------------------------------------------------------------
# The served path: the cases breaker-mesh-100k's probe holds through the door
# (cellbench/families/breaker.py), here on DefaultTokenService with a manual
# clock, each over the three strategies at the deployment's thresholds
# ---------------------------------------------------------------------------
def _completions(n_bad: int, n_good: int, strategy):
    """``(rt_ms, exc)`` of ``n_bad`` completions the strategy counts and
    ``n_good`` it does not (an RT of exactly the cutoff is not slow)."""
    if strategy == SLOW:
        return [51] * n_bad + [50] * n_good, [False] * (n_bad + n_good)
    return [5] * (n_bad + n_good), [True] * n_bad + [False] * n_good


@pytest.mark.parametrize("strategy, threshold, at, past", [
    (SLOW, 0.6, (6, 4), (7, 3)),  # 6 of 10 is the ratio, 7 of 10 is over it
    (ERR_RATIO, 0.5, (5, 5), (6, 4)),
    (ERR_COUNT, 4.0, (4, 6), (5, 5)),
], ids=["slow_ratio", "error_ratio", "error_count"])
class TestServedPath:
    REC = 2000
    T0 = 1_700_000_000_000

    def _service(self, clock, strategy, threshold):
        from sentinel_tpu.cluster.token_service import DefaultTokenService

        clock.set_ms(self.T0)
        svc = DefaultTokenService(
            EngineConfig(max_flows=16, max_namespaces=2, batch_size=64),
            serve_buckets=(64,), fuse_depths=())
        svc.load_rules([ClusterFlowRule(1, 1e9, namespace="a")])
        svc.load_degrade_rules([DegradeRule(
            1, strategy, threshold, slow_rt_ms=50, min_request_amount=5,
            stat_interval_ms=1000, recovery_timeout_ms=self.REC,
            namespace="a")])
        svc.warmup()  # the engine's clock starts here: T0 is its ms 1
        return svc

    def _at(self, clock, t_ms: int) -> None:
        clock.set_ms(self.T0 - 1 + t_ms)

    def _ask(self, svc, n: int = 1):
        status, remaining, _wait = svc.request_batch_arrays(np.ones(n, np.int64))
        return status.tolist(), remaining.tolist()

    def _tripped(self, clock, strategy, threshold, past):
        svc = self._service(clock, strategy, threshold)
        self._at(clock, 1000)
        svc.report_outcomes([1] * 10, *_completions(*past, strategy))
        self._at(clock, 1010)
        assert self._ask(svc, 2) == ([DEG] * 2, [self.REC] * 2)
        return svc

    def test_the_threshold_is_passed_strictly(self, manual_clock, strategy,
                                              threshold, at, past):
        svc = self._service(manual_clock, strategy, threshold)
        try:
            self._at(manual_clock, 1000)
            svc.report_outcomes([1] * 10, *_completions(*at, strategy))
            self._at(manual_clock, 1010)
            assert self._ask(svc, 3)[0] == [int(TokenStatus.OK)] * 3
            # one more of the bad kind takes it over
            svc.report_outcomes([1], *_completions(1, 0, strategy))
            self._at(manual_clock, 1020)
            assert self._ask(svc, 3) == ([DEG] * 3, [self.REC] * 3)
        finally:
            svc.close()

    def test_min_request_amount_from_both_sides(self, manual_clock, strategy,
                                                threshold, at, past):
        svc = self._service(manual_clock, strategy, threshold)
        try:
            self._at(manual_clock, 1000)
            # four completions, all bad: under min_request_amount 5
            svc.report_outcomes([1] * 4, *_completions(4, 0, strategy))
            self._at(manual_clock, 1010)
            assert self._ask(svc)[0] == [int(TokenStatus.OK)]
            # the fifth: five of five (ratio 1, count 5 over 4)
            svc.report_outcomes([1], *_completions(1, 0, strategy))
            self._at(manual_clock, 1020)
            assert self._ask(svc) == ([DEG], [self.REC])
        finally:
            svc.close()

    def test_one_probe_a_frame_after_the_timeout(self, manual_clock, strategy,
                                                 threshold, at, past):
        svc = self._tripped(manual_clock, strategy, threshold, past)
        try:
            self._at(manual_clock, 1010 + self.REC - 1)
            assert self._ask(svc, 4) == ([DEG] * 4, [1] * 4)
            self._at(manual_clock, 1010 + self.REC)
            status, remaining = self._ask(svc, 64)
            assert status == [int(TokenStatus.OK)] + [DEG] * 63
            assert remaining[1:] == [self.REC] * 63
        finally:
            svc.close()

    def test_a_sick_probe_rolls_back_and_a_healthy_one_closes(
            self, manual_clock, strategy, threshold, at, past):
        svc = self._tripped(manual_clock, strategy, threshold, past)
        try:
            t = 1010 + self.REC
            self._at(manual_clock, t)
            assert self._ask(svc)[0] == [int(TokenStatus.OK)]  # the probe
            svc.report_outcomes([1], *_completions(1, 0, strategy))
            self._at(manual_clock, t + 10)
            assert self._ask(svc, 2) == ([DEG] * 2, [self.REC - 10] * 2)
            t += self.REC
            self._at(manual_clock, t)
            assert self._ask(svc, 2)[0] == [int(TokenStatus.OK), DEG]
            svc.report_outcomes([1], *_completions(0, 1, strategy))
            self._at(manual_clock, t + 10)
            assert self._ask(svc, 3)[0] == [int(TokenStatus.OK)] * 3
        finally:
            svc.close()

    def test_the_fence_hides_what_was_reported_while_open(
            self, manual_clock, strategy, threshold, at, past):
        svc = self._tripped(manual_clock, strategy, threshold, past)
        try:
            # ten more bad completions three quarters through the timeout
            self._at(manual_clock, 1010 + 3 * self.REC // 4)
            svc.report_outcomes([1] * 10, *_completions(10, 0, strategy))
            t = 1010 + self.REC + 50
            self._at(manual_clock, t)
            assert self._ask(svc)[0] == [int(TokenStatus.OK)]  # the probe
            svc.report_outcomes([1], *_completions(0, 1, strategy))
            # closed: the ten are inside the stat interval and before the
            # fence, so they do not trip it again
            self._at(manual_clock, t + 20)
            assert self._ask(svc, 4)[0] == [int(TokenStatus.OK)] * 4
        finally:
            svc.close()
