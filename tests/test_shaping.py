"""Traffic-shaping parity: the vectorized warmup/pacing/borrow columns in
``_decide_core`` against a scalar reference port.

The scalar port below mirrors the engine's semantics op for op — including
the documented deviations from the upstream JVM controllers (sliding-window
``pass_qps`` instead of the previous-second counter; the refine-loop
admission; the own-cost-inclusive pacing prefix) — in ``np.float32``
arithmetic, so the parity assertions are exact equality, not tolerance
bands. Anything the port and the kernel disagree on is a real semantics
drift, not float noise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.engine import (
    ClusterFlowRule,
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
from sentinel_tpu.engine.rules import ControlBehavior, ThresholdMode
from sentinel_tpu.engine.state import flow_spec
from sentinel_tpu.stats import window as W

G = ThresholdMode.GLOBAL
B = ControlBehavior
CFG = EngineConfig(max_flows=32, max_namespaces=4, batch_size=64)

f32 = np.float32
NEVER = int(W.NEVER)

# ClusterEvent channels (engine/decide.py)
PASS, PASS_REQ, BLOCK, BLOCK_REQ, OCCUPIED_PASS, LEASED = range(6)


# ---------------------------------------------------------------------------
# scalar reference port
# ---------------------------------------------------------------------------
class ScalarRef:
    """Scalar mirror of ``_decide_core`` (single shard, f32 arithmetic).

    Windows are modeled exactly like ``stats/window.py``: one shared
    ``starts`` ring per window, mask-on-read, zero-on-rewrite. The shaping
    state is the per-flow (lpt, warm_tokens, warm_filled) triple.
    """

    def __init__(self, config, table):
        self.cfg = config
        self.spec = flow_spec(config)
        F, Bk = config.max_flows, self.spec.n_buckets
        t = jax.device_get(table)
        self.valid = np.asarray(t.valid)
        self.count = np.asarray(t.count, f32)
        self.mode = np.asarray(t.mode)
        self.ns_of = np.asarray(t.namespace_id)
        self.ns_max = np.asarray(t.ns_max_qps, f32)
        self.ns_conn = np.asarray(t.ns_connected)
        self.beh = np.asarray(t.behavior, np.int32)
        self.warn = np.asarray(t.warning_token, f32)
        self.max_tok = np.asarray(t.max_token, f32)
        self.slope = np.asarray(t.slope, f32)
        self.cold_cnt = np.asarray(t.cold_count, f32)
        self.maxq = np.asarray(t.max_queue_ms, np.int32)

        self.flow_starts = np.full(Bk, NEVER, np.int64)
        self.flow_counts = np.zeros((F, Bk, 6), np.int64)
        # the occupy ring is twice the flow window's: engine.state.occupy_ring
        self.occ_starts = np.full(2 * Bk, NEVER, np.int64)
        self.occ_counts = np.zeros((F, 2 * Bk, 1), np.int64)
        self.ns_starts = np.full(Bk, NEVER, np.int64)
        self.ns_counts = np.zeros((config.max_namespaces, Bk, 1), f32)

        self.lpt = np.full(F, NEVER, np.int64)
        self.warm_tokens = np.zeros(F, f32)
        self.warm_filled = np.full(F, NEVER, np.int64)

    # -- window helpers (mask-on-read, zero-on-rewrite) ---------------------
    def _valid_mask(self, starts, now):
        age = now - starts
        return (age >= 0) & (age < self.spec.interval_ms)

    def _win_sum(self, counts, starts, now, slot, ch):
        m = self._valid_mask(starts, now)
        return int(np.sum(counts[slot, m, ch]))

    def _future_sum(self, slot, now):
        ahead = self.occ_starts - now
        m = (ahead > 0) & (ahead <= self.spec.interval_ms)
        return int(np.sum(self.occ_counts[slot, m, 0]))

    def _roll(self, starts, counts, now):
        idx = (now // self.spec.bucket_ms) % self.spec.n_buckets
        cur = now - now % self.spec.bucket_ms
        if starts[idx] != cur:
            counts[:, idx, :] = 0
            starts[idx] = cur
        return idx

    def _passed(self, slot, now):
        return f32(
            self._win_sum(self.flow_counts, self.flow_starts, now, slot, PASS)
            + self._win_sum(self.occ_counts, self.occ_starts, now, slot, 0)
            + self._win_sum(
                self.flow_counts, self.flow_starts, now, slot, LEASED
            )
        )

    # -- the decision step --------------------------------------------------
    def step(self, now, rows):
        """``rows``: [(slot, acquire, prioritized)] — the live batch prefix.

        Returns (status, wait_ms, remaining) int arrays of len(rows),
        mirroring the engine verdict triple for the live rows.
        """
        cfg, spec = self.cfg, self.spec
        n = len(rows)
        slot = np.array([r[0] for r in rows], np.int64)
        acq = np.array([r[1] for r in rows], np.int64)
        prio = np.array([r[2] for r in rows], bool)
        acq_f = acq.astype(f32)
        safe = np.where(slot >= 0, slot, 0)

        owned = (slot >= 0) & self.valid[safe]
        no_rule = ~owned
        live = owned.copy()

        # namespace guard: precise arm (equivalent to the fast arm whenever
        # the budget boundary is not inside the batch)
        ns_id = np.where(owned, self.ns_of[safe], 0)
        ns_budget = self.ns_max * f32(spec.interval_ms / 1000.0)
        m = self._valid_mask(self.ns_starts, now)
        ns_already = self.ns_counts[:, m, 0].sum(axis=1).astype(f32)
        ns_seen = np.zeros(cfg.max_namespaces, f32)
        ns_ok = np.zeros(n, bool)
        for i in range(n):
            if not live[i]:
                continue
            k = ns_id[i]
            ns_ok[i] = (
                f32(ns_already[k] + ns_seen[k]) + f32(1.0) <= ns_budget[k]
            )
            ns_seen[k] += f32(1.0)
        too_many = live & ~ns_ok
        active = live & ns_ok

        is_warm = (self.beh[safe] == 1) | (self.beh[safe] == 3)
        is_pace = (self.beh[safe] == 2) | (self.beh[safe] == 3)
        warm_rows = active & is_warm
        pace_try = active & is_pace
        active_window = active & ~is_pace

        cnt = self.count[safe]
        cnt_safe = np.maximum(cnt, f32(1e-6))
        conn = self.ns_conn[ns_id].astype(f32)
        factor = np.where(
            self.mode[safe] == int(ThresholdMode.AVG_LOCAL), conn, f32(1.0)
        )
        passed = np.array([self._passed(s, now) for s in safe], f32)

        # 2b. warmup sync (per-flow; duplicate rows see identical values)
        qps = cnt.copy()
        if warm_rows.any():
            pass_qps = passed * f32(1000.0 / spec.interval_ms)
            cur_sec = now - now % 1000
            synced_slots = {}
            for i in range(n):
                s = safe[i]
                tokens = self.warm_tokens[s]
                filled = self.warm_filled[s]
                can_refill = (tokens < self.warn[s]) | (
                    (tokens > self.warn[s]) & (pass_qps[i] < self.cold_cnt[s])
                )
                elapsed = f32(cur_sec - filled)
                cooled = min(
                    f32(
                        tokens
                        + (
                            f32(elapsed * cnt_safe[i]) / f32(1000.0)
                            if can_refill
                            else f32(0.0)
                        )
                    ),
                    self.max_tok[s],
                )
                synced = max(f32(cooled - pass_qps[i]), f32(0.0))
                do_sync = warm_rows[i] and cur_sec > filled
                tokens_new = synced if do_sync else tokens
                above = max(f32(tokens_new - self.warn[s]), f32(0.0))
                warning_qps = f32(1.0) / f32(
                    f32(above * self.slope[s]) + f32(1.0) / cnt_safe[i]
                )
                if warm_rows[i] and tokens_new >= self.warn[s]:
                    qps[i] = warning_qps
                if do_sync:
                    synced_slots[int(s)] = (tokens_new, cur_sec)
            for s, (tok, sec) in synced_slots.items():
                self.warm_tokens[s] = tok
                self.warm_filled[s] = sec

        rate_qps = qps * factor * f32(cfg.exceed_count)
        threshold = rate_qps * f32(spec.interval_ms / 1000.0)

        # 3. refine-loop window admission (mirrors the non-uniform path)
        def excl_prefix(mask, contrib):
            run, out = {}, np.zeros(n, f32)
            for i in range(n):
                s = int(safe[i])
                out[i] = run.get(s, f32(0.0))
                if mask[i]:
                    run[s] = f32(out[i] + contrib[i])
            return out

        admit = active_window.copy()
        for _ in range(cfg.admission_refine_iters):
            prefix = excl_prefix(admit, acq_f)
            admit = active_window & (
                f32(passed + prefix) + acq_f <= threshold
            )
        admitted_prefix = excl_prefix(admit, acq_f)

        # 3b. pacing (own-cost-inclusive prefix, refine to a fixpoint)
        pace_wait = np.zeros(n, np.int64)
        pace_admit = np.zeros(n, bool)
        l_rel = np.zeros(n, f32)
        if pace_try.any():
            cost = np.round(
                f32(1000.0) * acq_f / np.maximum(rate_qps, f32(1e-6))
            ).astype(f32)
            rel0 = np.maximum(self.lpt[safe] - now, -(2**20)).astype(f32)
            maxq = self.maxq[safe].astype(f32)

            def pace_pass(accept):
                c_first = {}
                for i in range(n):
                    if accept[i] and int(safe[i]) not in c_first:
                        c_first[int(safe[i])] = cost[i]
                incl = excl_prefix(accept, cost) + cost
                out = np.zeros(n, f32)
                for i in range(n):
                    cf = c_first.get(int(safe[i]), f32(0.0))
                    out[i] = f32(np.maximum(rel0[i], -cf) + incl[i])
                return out

            accept = pace_try.copy()
            l_rel = pace_pass(accept)
            for _ in range(cfg.admission_refine_iters):
                accept = pace_try & (l_rel <= maxq)
                l_rel = pace_pass(accept)
            accept = pace_try & (l_rel <= maxq)
            pace_admit = accept
            pace_wait = np.maximum(l_rel, f32(0.0)).astype(np.int64)
            for i in range(n):
                if accept[i]:
                    s = int(safe[i])
                    self.lpt[s] = max(
                        self.lpt[s], now + int(np.round(l_rel[i]))
                    )
        pace_now = pace_admit & (pace_wait == 0)
        pace_later = pace_admit & (pace_wait > 0)
        pace_reject = pace_try & ~pace_admit

        # 4. priority occupy (DEFAULT-behavior rows only)
        blocked = active_window & ~admit
        wait_next = spec.bucket_ms - now % spec.bucket_ms
        try_occ = blocked & prio & (self.beh[safe] == 0)
        can_occupy = np.zeros(n, bool)
        if prio.any():
            next_start = now + wait_next
            horizon = next_start - spec.interval_ms
            cur_valid = self._valid_mask(self.flow_starts, now)
            exp_mask = cur_valid & (self.flow_starts <= horizon)
            occ_prefix = excl_prefix(try_occ, acq_f)
            for i in range(n):
                if not try_occ[i]:
                    continue
                s = safe[i]
                expiring = f32(self.flow_counts[s, exp_mask, PASS].sum())
                waiting = f32(self._future_sum(s, now))
                lhs = f32(
                    f32(
                        f32(
                            f32(passed[i] - expiring) + admitted_prefix[i]
                        )
                        + waiting
                    )
                    + occ_prefix[i]
                ) + acq_f[i]
                can_occupy[i] = lhs <= f32(cfg.max_occupy_ratio) * threshold[i]
        hard_block = blocked & ~can_occupy

        # 5. window updates
        idx = self._roll(self.flow_starts, self.flow_counts, now)
        admit_i = admit | pace_now
        hard_i = hard_block | pace_reject
        for i in range(n):
            s = safe[i]
            if admit_i[i]:
                self.flow_counts[s, idx, PASS] += acq[i]
                self.flow_counts[s, idx, PASS_REQ] += 1
            if hard_i[i]:
                self.flow_counts[s, idx, BLOCK] += acq[i]
                self.flow_counts[s, idx, BLOCK_REQ] += 1
            if admit[i] and prio[i]:
                self.flow_counts[s, idx, OCCUPIED_PASS] += acq[i]
        charge_wait = np.where(can_occupy, wait_next, pace_wait)
        charge_valid = can_occupy | pace_later
        if (prio.any() or pace_try.any()) and charge_valid.any():
            cur_start = now - now % spec.bucket_ms
            for i in range(n):
                if not (charge_valid[i] and charge_wait[i] > 0):
                    continue
                k = (now + charge_wait[i] - cur_start) // spec.bucket_ms
                k = min(max(int(k), 1), spec.n_buckets - 1)
                start = cur_start + k * spec.bucket_ms
                oi = (start // spec.bucket_ms) % len(self.occ_starts)
                if self.occ_starts[oi] != start:
                    self.occ_counts[:, oi, :] = 0
                    self.occ_starts[oi] = start
                self.occ_counts[safe[i], oi, 0] += acq[i]
        nsi = self._roll(self.ns_starts, self.ns_counts, now)
        for i in range(n):
            if live[i] and ns_ok[i]:
                self.ns_counts[ns_id[i], nsi, 0] += f32(1.0)

        # 6. verdicts
        status = np.full(n, int(TokenStatus.FAIL), np.int64)
        status[no_rule] = int(TokenStatus.NO_RULE_EXISTS)
        status[too_many] = int(TokenStatus.TOO_MANY_REQUEST)
        status[admit | pace_now] = int(TokenStatus.OK)
        status[can_occupy | pace_later] = int(TokenStatus.SHOULD_WAIT)
        status[hard_block | pace_reject] = int(TokenStatus.BLOCKED)
        wait = np.where(
            can_occupy, wait_next, np.where(pace_later, pace_wait, 0)
        )
        rem_f = np.clip(
            f32(f32(threshold - passed) - admitted_prefix) - np.where(
                admit, acq_f, f32(0.0)
            ),
            f32(0.0),
            f32(2**30),
        )
        remaining = np.where(admit, rem_f.astype(np.int64), 0)
        return status, wait, remaining


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------
def _rules():
    return [
        ClusterFlowRule(flow_id=1, count=50.0, mode=G),
        ClusterFlowRule(
            flow_id=2, count=100.0, mode=G,
            control_behavior=B.WARM_UP, warm_up_period_sec=10, cold_factor=3,
        ),
        ClusterFlowRule(
            flow_id=3, count=40.0, mode=G,
            control_behavior=B.RATE_LIMITER, max_queueing_time_ms=400,
        ),
        ClusterFlowRule(
            flow_id=4, count=80.0, mode=G,
            control_behavior=B.WARM_UP_RATE_LIMITER,
            warm_up_period_sec=5, cold_factor=4, max_queueing_time_ms=300,
        ),
        ClusterFlowRule(flow_id=5, count=20.0, mode=G),
    ]


def _build(cfg=CFG):
    table, index = build_rule_table(cfg, _rules())
    return table, index


def _run_engine(state, table, now, rows, cfg=CFG):
    slots = [r[0] for r in rows]
    acq = [r[1] for r in rows]
    prio = [r[2] for r in rows]
    batch = make_batch(cfg, slots, acq, prio)
    return decide(cfg, state, table, batch, jnp.int32(now))


def _verdict_rows(v, n):
    return (
        np.asarray(v.status)[:n].astype(np.int64),
        np.asarray(v.wait_ms)[:n].astype(np.int64),
        np.asarray(v.remaining)[:n].astype(np.int64),
    )


# ---------------------------------------------------------------------------
# column precompute vs the reference WarmUpController formulas
# ---------------------------------------------------------------------------
class TestColumnPrecompute:
    def test_warmup_columns_match_reference_construct(self):
        table, index = _build()
        s = index.lookup(2)
        c, cold, period = 100.0, 3, 10
        warn = int(period * c / (cold - 1))
        max_tok = int(warn + 2.0 * period * c / (1.0 + cold))
        assert float(table.warning_token[s]) == warn
        assert float(table.max_token[s]) == max_tok
        assert float(table.slope[s]) == pytest.approx(
            (cold - 1.0) / c / (max_tok - warn)
        )
        assert float(table.cold_count[s]) == int(c) // cold

    def test_max_queue_clamped_to_borrowable_horizon(self):
        cfg = CFG
        table, index = build_rule_table(cfg, [
            ClusterFlowRule(
                flow_id=9, count=10.0, mode=G,
                control_behavior=B.RATE_LIMITER,
                max_queueing_time_ms=10_000,
            ),
        ])
        cap = (cfg.n_buckets - 1) * cfg.bucket_ms
        assert int(table.max_queue_ms[index.lookup(9)]) == cap

    def test_plain_rules_have_inert_columns(self):
        table, index = _build()
        s = index.lookup(1)
        assert int(table.behavior[s]) == 0
        assert int(table.max_queue_ms[s]) == 0
        assert float(table.max_token[s]) == 0.0


# ---------------------------------------------------------------------------
# warmup curve shape
# ---------------------------------------------------------------------------
class TestWarmupCurve:
    def test_cold_start_admits_count_over_cold_factor(self):
        """A fully cold flow (token bucket at maxToken) must admit at the
        cold rate count/coldFactor; at/below the warning line it admits the
        full count."""
        table, index = _build()
        state = make_state(CFG)
        slot = index.lookup(2)  # count=100, cold=3 → cold rate ~33
        state, v = _run_engine(
            state, table, 10_000, [(slot, 1, False)] * 60
        )
        ok = int((np.asarray(v.status)[:60] == TokenStatus.OK).sum())
        # first sync clamps tokens to maxToken → slope floor ≈ count/cold
        assert 30 <= ok <= 34

    def test_warm_flow_admits_full_count(self):
        """Below the warning line the full count applies. The state is
        injected directly: driving the bucket down through traffic alone
        oscillates at the refill boundary (the sliding-window pass_qps
        dips below cold_count between batches and refills — the documented
        deviation from the reference's previous-second counter)."""
        table, index = _build()
        state = make_state(CFG)
        slot = index.lookup(2)
        now = 10_000
        warn = float(np.asarray(table.warning_token)[slot])
        # tokens below the knee; filled stamp at the current second so the
        # first batch does not re-sync (which would refill the idle gap)
        shaping = state.shaping._replace(
            warm_tokens=state.shaping.warm_tokens.at[slot].set(warn - 100.0),
            warm_filled=state.shaping.warm_filled.at[slot].set(
                now - now % 1000
            ),
        )
        state = state._replace(shaping=shaping)
        state, v = _run_engine(state, table, now, [(slot, 1, False)] * 64)
        ok = int((np.asarray(v.status) == TokenStatus.OK).sum())
        assert ok == 64  # below the knee the full count=100 applies

    def test_knee_rate_matches_slope_formula(self):
        """At the slope knee (tokens == warningToken) the admitted rate is
        exactly count; at maxToken it is count/coldFactor."""
        table, index = _build()
        slot = index.lookup(2)
        cnt = float(np.asarray(table.count)[slot])
        warn = float(np.asarray(table.warning_token)[slot])
        max_tok = float(np.asarray(table.max_token)[slot])
        slope = float(np.asarray(table.slope)[slot])
        qps_at = lambda tok: 1.0 / (max(tok - warn, 0.0) * slope + 1.0 / cnt)
        assert qps_at(warn) == pytest.approx(cnt)
        assert qps_at(max_tok) == pytest.approx(cnt / 3.0, rel=0.05)
        # monotone: draining tokens raises the admitted rate
        qs = [qps_at(t) for t in np.linspace(max_tok, warn, 20)]
        assert all(b >= a for a, b in zip(qs, qs[1:]))


# ---------------------------------------------------------------------------
# pacing closed form
# ---------------------------------------------------------------------------
class TestPacing:
    def test_waits_are_spaced_by_cost_and_capped(self):
        table, index = _build()
        state = make_state(CFG)
        slot = index.lookup(3)  # count=40 → cost 25ms, maxq=400
        state, v = _run_engine(state, table, 10_000, [(slot, 1, False)] * 40)
        st, wait, _ = _verdict_rows(v, 40)
        ok = st == TokenStatus.OK
        sw = st == TokenStatus.SHOULD_WAIT
        rj = st == TokenStatus.BLOCKED
        # first row passes now; the queue builds in 25ms steps up to 400ms
        assert ok[0] and wait[0] == 0
        accepted_waits = wait[ok | sw]
        assert list(accepted_waits) == [25 * i for i in range(len(accepted_waits))]
        assert accepted_waits.max() <= 400
        # the tail beyond the queue cap rejects, as a suffix
        assert rj.sum() == 40 - len(accepted_waits)
        assert rj[-1] and not rj[0]

    def test_lpt_monotone_and_respected_across_batches(self):
        table, index = _build()
        state = make_state(CFG)
        slot = index.lookup(3)
        now, prev_lpt = 10_000, NEVER
        rng = np.random.default_rng(7)
        for _ in range(8):
            state, v = _run_engine(
                state, table, now, [(slot, 1, False)] * int(rng.integers(1, 12))
            )
            lpt = int(np.asarray(state.shaping.lpt)[slot])
            assert lpt >= prev_lpt
            prev_lpt = lpt
            now += int(rng.integers(5, 120))

    def test_paced_rows_report_zero_remaining(self):
        table, index = _build()
        state = make_state(CFG)
        slot = index.lookup(3)
        state, v = _run_engine(state, table, 10_000, [(slot, 1, False)] * 4)
        st, _, rem = _verdict_rows(v, 4)
        assert (st != TokenStatus.BLOCKED).all()
        assert (rem == 0).all()


# ---------------------------------------------------------------------------
# cross-batch SHOULD_WAIT carry (the future-window borrow)
# ---------------------------------------------------------------------------
class TestCrossBatchBorrow:
    def test_pace_later_charges_future_window(self):
        table, index = _build()
        state = make_state(CFG)
        slot = index.lookup(3)
        now = 10_000
        state, v = _run_engine(state, table, now, [(slot, 1, False)] * 10)
        st, wait, _ = _verdict_rows(v, 10)
        later = (st == TokenStatus.SHOULD_WAIT)
        assert later.sum() > 0
        spec = flow_spec(CFG)
        fut = int(W.future_sum_at(
            spec, state.occupy, jnp.int32(now), 0,
            jnp.asarray([slot]),
        )[0])
        assert fut == int(later.sum())

    def test_borrow_matures_into_passed_no_overadmission(self):
        """The borrowed tokens fold into the PASS read once their window
        matures: a WARM_UP_RATE_LIMITER flow's warmup sync sees paced
        SHOULD_WAIT traffic as passed load, so the shaper cannot be
        over-refilled by tokens that are merely queued."""
        table, index = _build()
        state = make_state(CFG)
        slot = index.lookup(3)
        now = 10_000
        state, v = _run_engine(state, table, now, [(slot, 1, False)] * 10)
        st, wait, _ = _verdict_rows(v, 10)
        w_max = int(wait.max())
        assert w_max > 0
        spec = flow_spec(CFG)
        matured = int(W.window_sum_at(
            spec, state.occupy, jnp.int32(now + w_max), 0,
            jnp.asarray([slot]),
        )[0])
        assert matured == int((st == TokenStatus.SHOULD_WAIT).sum())


# ---------------------------------------------------------------------------
# scalar parity on seeded mixed-behavior streams
# ---------------------------------------------------------------------------
class TestScalarParity:
    @pytest.mark.parametrize("seed", range(4))
    def test_zipf_stream_parity(self, seed):
        table, index = _build()
        state = make_state(CFG)
        ref = ScalarRef(CFG, table)
        slots = [index.lookup(f) for f in (1, 2, 3, 4, 5)]
        rng = np.random.default_rng(seed)
        # Zipf-weighted flow popularity (bounded to the 5 rule slots)
        zipf = 1.0 / np.arange(1, 6) ** 1.1
        zipf /= zipf.sum()
        now = 10_000
        for step in range(12):
            n = int(rng.integers(4, 48))
            picks = rng.choice(5, size=n, p=zipf)
            rows = [
                (
                    slots[p] if rng.random() > 0.03 else -1,  # rare no-rule
                    int(rng.integers(1, 4)),
                    bool(rng.random() < 0.15),
                )
                for p in picks
            ]
            state, v = _run_engine(state, table, now, rows)
            st_e, wait_e, rem_e = _verdict_rows(v, n)
            st_s, wait_s, rem_s = ref.step(now, rows)
            np.testing.assert_array_equal(
                st_e, st_s, err_msg=f"seed={seed} step={step} status"
            )
            np.testing.assert_array_equal(
                wait_e, wait_s, err_msg=f"seed={seed} step={step} wait"
            )
            np.testing.assert_array_equal(
                rem_e, rem_s, err_msg=f"seed={seed} step={step} remaining"
            )
            # shaper state parity, not just verdicts
            np.testing.assert_array_equal(
                np.asarray(state.shaping.lpt)[slots],
                ref.lpt[slots],
                err_msg=f"seed={seed} step={step} lpt",
            )
            np.testing.assert_allclose(
                np.asarray(state.shaping.warm_tokens)[slots],
                ref.warm_tokens[slots],
                rtol=0, atol=0,
                err_msg=f"seed={seed} step={step} warm_tokens",
            )
            now += int(rng.integers(10, 700))

    def test_warmup_ramp_parity(self):
        """Cold-start ramp: a warmup flow driven at its full count for many
        seconds — the scalar port and the kernel must agree on every verdict
        while the token bucket drains through the knee."""
        table, index = _build()
        state = make_state(CFG)
        ref = ScalarRef(CFG, table)
        slot = index.lookup(2)
        now = 5_000
        for step in range(20):
            rows = [(slot, 1, False)] * 50
            state, v = _run_engine(state, table, now, rows)
            st_e, wait_e, rem_e = _verdict_rows(v, 50)
            st_s, wait_s, rem_s = ref.step(now, rows)
            np.testing.assert_array_equal(st_e, st_s, err_msg=f"step={step}")
            np.testing.assert_array_equal(rem_e, rem_s)
            np.testing.assert_array_equal(
                np.asarray(state.shaping.warm_tokens)[slot],
                ref.warm_tokens[slot],
            )
            now += 500


# ---------------------------------------------------------------------------
# fused / sharded bit-identity with shaping active
# ---------------------------------------------------------------------------
def _random_frames(index, rng, depth, n=48):
    frames = []
    for _ in range(depth):
        flows = rng.integers(1, 6, size=n)
        rows = [
            (index.lookup(int(f)), int(rng.integers(1, 3)),
             bool(rng.random() < 0.2))
            for f in flows
        ]
        frames.append(rows)
    return frames


class TestFusedParity:
    def test_fused_chain_matches_sequential_decides(self):
        depth = 4
        table, index = _build()
        rng = np.random.default_rng(11)
        frames = _random_frames(index, rng, depth)
        now = 10_000

        state_seq = make_state(CFG)
        seq_verdicts = []
        for rows in frames:
            state_seq, v = _run_engine(state_seq, table, now, rows)
            seq_verdicts.append(v)

        fused = decide_fused_donating(CFG, depth)
        batches = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[
                make_batch(
                    CFG,
                    [r[0] for r in rows],
                    [r[1] for r in rows],
                    [r[2] for r in rows],
                )
                for rows in frames
            ],
        )
        state_f, vf = fused(make_state(CFG), table, pack_batch(batches, now))
        vf = unpack_verdicts(vf)

        for k, v in enumerate(seq_verdicts):
            np.testing.assert_array_equal(
                np.asarray(vf.status)[k], np.asarray(v.status),
                err_msg=f"frame {k}",
            )
            np.testing.assert_array_equal(
                np.asarray(vf.wait_ms)[k], np.asarray(v.wait_ms)
            )
        np.testing.assert_array_equal(
            np.asarray(state_f.shaping.lpt),
            np.asarray(state_seq.shaping.lpt),
        )
        np.testing.assert_array_equal(
            np.asarray(state_f.shaping.warm_tokens),
            np.asarray(state_seq.shaping.warm_tokens),
        )


class TestShardedParity:
    def test_sharded_matches_single_device_with_shaping(self):
        from sentinel_tpu.parallel import (
            make_flow_mesh,
            make_sharded_decide,
            shard_rules,
            shard_state,
        )

        assert len(jax.devices()) == 8
        mesh = make_flow_mesh()
        table, index = _build()
        sharded_step = make_sharded_decide(CFG, mesh)
        state_1 = make_state(CFG)
        state_8 = shard_state(make_state(CFG), mesh)
        table_8 = shard_rules(table, mesh)
        rng = np.random.default_rng(3)
        now = 10_000
        for step in range(8):
            rows = _random_frames(index, rng, 1)[0]
            batch = make_batch(
                CFG,
                [r[0] for r in rows],
                [r[1] for r in rows],
                [r[2] for r in rows],
            )
            state_1, v1 = decide(CFG, state_1, table, batch, jnp.int32(now))
            state_8, v8 = sharded_step(state_8, table_8, batch, jnp.int32(now))
            np.testing.assert_array_equal(
                np.asarray(v1.status), np.asarray(v8.status),
                err_msg=f"step {step}",
            )
            np.testing.assert_array_equal(
                np.asarray(v1.wait_ms), np.asarray(v8.wait_ms)
            )
            np.testing.assert_array_equal(
                np.asarray(v1.remaining), np.asarray(v8.remaining)
            )
            now += int(rng.integers(20, 400))
        # gathered shard state equals the single-device shaper state
        np.testing.assert_array_equal(
            np.asarray(state_1.shaping.lpt),
            np.asarray(jax.device_get(state_8.shaping.lpt)).reshape(-1),
        )


# ---------------------------------------------------------------------------
# shaped rules refuse leases (client-local admission would bypass the shaper)
# ---------------------------------------------------------------------------
class TestShapedNotLeasable:
    def test_lease_grant_refused_for_shaped_rule(self, manual_clock):
        from sentinel_tpu.cluster.token_service import DefaultTokenService

        svc = DefaultTokenService(CFG)
        svc.load_rules(_rules())
        for fid in (2, 3, 4):
            assert svc.lease_grant(fid, want=8).status == int(
                TokenStatus.NOT_LEASABLE
            )
        assert svc.lease_grant(1, want=8).ok
