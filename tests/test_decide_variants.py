"""Bitwise parity of the variants of the served decide step.

One function decides (``engine/decide._decide_core``); what is left to hold
together are its forks. The serving path jits it with ``grouped=True`` (the
host batcher's same-flow-rows-contiguous layout, sort-free segment prefix)
and ``uniform`` set per batch, chains it under ``lax.scan`` in the fused
steps, and shards it over a flow mesh. Each of these is held here to the
plain form it stands in for, ``decide(grouped=False, uniform=False)`` under
both general segment-prefix implementations, a run of single donated steps,
the single-shard step: every verdict field and every state leaf comes back
*bit-identical*, across mixed control behaviors (DEFAULT / WARM_UP /
RATE_LIMITER / WARM_UP_RATE_LIMITER), both threshold modes, prioritized
occupy borrows, namespace-guard boundary crossings, window rolls and idle
gaps, unknown flows, breakers fed by the outcome step and half-open probes.
The fused programs run in no cell of the benchmark; this file is their guard.

Equality is ``==`` on raw arrays, never ``allclose``: any divergence is a
semantics drift in one of the forks, not float noise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.engine import (
    ClusterFlowRule,
    EngineConfig,
    build_rule_table,
    decide,
    make_batch,
    make_state,
)
from sentinel_tpu.engine.decide import (
    decide_donating,
    decide_fused_donating,
    pack_batch,
    unpack_verdicts,
)
from sentinel_tpu.engine import DegradeRule, DegradeStrategy, TokenStatus
from sentinel_tpu.engine.outcome import outcome_step_donating
from sentinel_tpu.engine.rules import ControlBehavior, ThresholdMode
from sentinel_tpu.engine.state import BR_CLOSED
from sentinel_tpu.parallel import (
    make_flow_mesh,
    make_sharded_decide,
    shard_rules,
    shard_state,
)

G = ThresholdMode.GLOBAL
CB = ControlBehavior

CFG = EngineConfig(max_flows=32, max_namespaces=4, batch_size=64)
PREFIX_IMPLS = ("matmul", "sort")  # what "auto" chooses between, ungrouped


def _mixed_rules():
    """Every control behavior, both threshold modes, two namespaces — one
    of them ("tight") with a guard budget small enough that batches cross
    its boundary (exercising the precise ns-guard arm)."""
    return [
        ClusterFlowRule(flow_id=0, count=6.0, mode=G),
        ClusterFlowRule(flow_id=1, count=50.0, mode=G),
        ClusterFlowRule(flow_id=2, count=5.0),  # AVG_LOCAL
        ClusterFlowRule(
            flow_id=3, count=40.0, mode=G, control_behavior=CB.WARM_UP
        ),
        ClusterFlowRule(
            flow_id=4, count=25.0, mode=G,
            control_behavior=CB.RATE_LIMITER, max_queueing_time_ms=300,
        ),
        ClusterFlowRule(
            flow_id=5, count=30.0, mode=G,
            control_behavior=CB.WARM_UP_RATE_LIMITER,
            max_queueing_time_ms=200,
        ),
        ClusterFlowRule(flow_id=6, count=9.0, mode=G, namespace="tight"),
        ClusterFlowRule(flow_id=7, count=7.0, mode=G, namespace="tight"),
    ]


def _build(config):
    table, index = build_rule_table(
        config, _mixed_rules(), ns_max_qps=30_000.0,
        connected={"default": 3, "tight": 2},
    )
    # shrink the "tight" namespace guard so seeded streams cross it
    ns_tight = index.namespace_slot("tight")
    table = table._replace(
        ns_max_qps=table.ns_max_qps.at[ns_tight].set(12.0)
    )
    return table, index


def _stream(rng, config, steps, uniform):
    """Seeded grouped request stream with rolls, idle gaps, unknown flows,
    prioritized rows and (non-uniform) mixed acquire sizes."""
    now = 10_000
    known = [0, 1, 2, 3, 4, 5, 6, 7]
    for _ in range(steps):
        n = int(rng.integers(4, config.batch_size - 3))
        slots = rng.choice(known + [29], size=n).astype(np.int32)  # 29: no rule
        slots.sort()  # the grouped-batch contract
        acq = (
            np.ones(n, np.int32)
            if uniform
            else rng.integers(1, 4, size=n).astype(np.int32)
        )
        prio = rng.random(n) < 0.3
        batch = make_batch(config, slots, acq, prio)
        yield now, batch
        # mostly intra-bucket advances, sometimes a roll, rarely a long gap
        r = rng.random()
        now += int(
            rng.integers(5, 60) if r < 0.7
            else rng.integers(100, 350) if r < 0.95
            else rng.integers(1_500, 2_600)
        )


def _assert_trees_equal(a, b, label):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype, f"{label}: dtype {x.dtype} vs {y.dtype}"
        np.testing.assert_array_equal(x, y, err_msg=label)


def _packed_frames(frames, now):
    """The fused step's one host argument: the frames stacked, one clock."""
    batches = jax.tree.map(lambda *xs: np.stack(xs), *frames)
    return pack_batch(batches, now)


def _assert_fused_equals_chain(config, table, state_f, state_s, frames, now,
                               uniform, label):
    """``decide_fused_donating(depth)`` against ``depth`` consecutive
    ``decide_donating`` calls at one clock: frame ``k`` of the fused verdict
    buffer equals the ``k``-th single step's, and the final states agree.
    Both states are donated; returns the fused side's verdicts unpacked."""
    depth = len(frames)
    fused = decide_fused_donating(config, depth, grouped=True, uniform=uniform)
    single = decide_donating(config, grouped=True, uniform=uniform)
    state_f, v_f = fused(state_f, table, _packed_frames(frames, now))
    v_f = np.asarray(v_f)
    assert v_f.shape == (3, depth, config.batch_size)
    for k, batch in enumerate(frames):
        state_s, v_s = single(state_s, table, pack_batch(batch, now))
        v_s = np.asarray(v_s)
        assert v_s.dtype == v_f.dtype
        np.testing.assert_array_equal(
            v_f[:, k], v_s, err_msg=f"{label}: verdicts of frame {k}"
        )
    _assert_trees_equal(state_f, state_s, f"{label}: state")
    return unpack_verdicts(v_f)


class TestVariantParity:
    @pytest.mark.parametrize("prefix_impl", PREFIX_IMPLS)
    @pytest.mark.parametrize("uniform", [False, True])
    @pytest.mark.parametrize("seed", range(3))
    def test_stream_parity_single_shard(self, seed, uniform, prefix_impl):
        """The served fast path against the general path, whichever general
        segment prefix it is built with."""
        general = CFG._replace(prefix_impl=prefix_impl)
        table, _ = _build(CFG)
        rng = np.random.default_rng(seed)
        st_f, st_g = make_state(CFG), make_state(general)
        for step_i, (now, batch) in enumerate(
            _stream(rng, CFG, steps=10, uniform=uniform)
        ):
            st_f, v_f = decide(
                CFG, st_f, table, batch, now, grouped=True, uniform=uniform
            )
            st_g, v_g = decide(general, st_g, table, batch, now)
            _assert_trees_equal(
                v_f, v_g, f"verdicts seed={seed} step={step_i}"
            )
            _assert_trees_equal(
                st_f, st_g, f"state seed={seed} step={step_i}"
            )

    @pytest.mark.parametrize("seed", range(3))
    def test_uniform_flag_is_only_a_fast_path(self, seed):
        """On a stream whose acquires are all 1 the ``uniform=True`` program
        (no refinement passes) decides what the mixed-acquire program does."""
        table, _ = _build(CFG)
        rng = np.random.default_rng(100 + seed)
        st_u, st_m = make_state(CFG), make_state(CFG)
        for step_i, (now, batch) in enumerate(
            _stream(rng, CFG, steps=10, uniform=True)
        ):
            st_u, v_u = decide(
                CFG, st_u, table, batch, now, grouped=True, uniform=True
            )
            st_m, v_m = decide(
                CFG, st_m, table, batch, now, grouped=True, uniform=False
            )
            _assert_trees_equal(
                v_u, v_m, f"verdicts seed={seed} step={step_i}"
            )
            _assert_trees_equal(
                st_u, st_m, f"state seed={seed} step={step_i}"
            )

    def test_prioritized_occupy_parity(self):
        """Saturate a flow so prioritized rows reach the occupy/borrow arm
        (SHOULD_WAIT + future-window charge) on both paths."""
        table, _ = _build(CFG)
        st_f, st_g = make_state(CFG), make_state(CFG)

        def both(batch, now):
            nonlocal st_f, st_g
            st_f, v_f = decide(CFG, st_f, table, batch, now, grouped=True)
            st_g, v_g = decide(CFG, st_g, table, batch, now)
            _assert_trees_equal(v_f, v_g, f"occupy verdicts now={now}")
            _assert_trees_equal(st_f, st_g, f"occupy state now={now}")
            return v_f

        # fill flow 0 (count 6 → window budget 6) at the window's start …
        both(make_batch(CFG, np.zeros(6, np.int32)), 50_000)
        # … then near its end: passed=6 blocks everyone, but those 6 tokens
        # expire by the next bucket, so prioritized rows can borrow ahead
        prio = np.ones(4, bool)
        v = both(
            make_batch(CFG, np.zeros(4, np.int32), np.ones(4, np.int32),
                       prio),
            50_950,
        )
        waits = np.asarray(v.wait_ms)[:4]
        assert (waits > 0).any()  # the borrow arm actually fired
        # matured borrows fold into the PASS read of the next window
        both(make_batch(CFG, np.zeros(8, np.int32)), 51_010)

    @pytest.mark.parametrize("uniform", [False, True])
    @pytest.mark.parametrize("depth", [2, 4, 8])  # the served fuse depths
    def test_fused_scan_parity(self, depth, uniform):
        table, _ = _build(CFG)
        rng = np.random.default_rng(7)
        frames = list(_stream(rng, CFG, steps=depth, uniform=uniform))
        _assert_fused_equals_chain(
            CFG, table, make_state(CFG), make_state(CFG),
            [b for _, b in frames], frames[0][0], uniform,
            f"fused depth={depth} uniform={uniform}",
        )

    def test_sharded_parity_8dev(self):
        """The sharded step over 8 virtual devices against the single-shard
        step on the same stream."""
        assert len(jax.devices()) == 8, "conftest provides 8 virtual devices"
        cfg = CFG._replace(max_flows=64)
        table, _ = _build(cfg)
        mesh = make_flow_mesh()
        step_m = make_sharded_decide(cfg, mesh, grouped=True)
        st_m = shard_state(make_state(cfg), mesh)
        st_1 = make_state(cfg)
        tbl = shard_rules(table, mesh)
        rng = np.random.default_rng(11)
        for step_i, (now, batch) in enumerate(
            _stream(rng, cfg, steps=6, uniform=False)
        ):
            st_m, v_m = step_m(st_m, tbl, batch, now)
            st_1, v_1 = decide(cfg, st_1, table, batch, now, grouped=True)
            _assert_trees_equal(v_m, v_1, f"sharded verdicts step={step_i}")
            _assert_trees_equal(
                jax.device_get(st_m), st_1, f"sharded state step={step_i}"
            )

    def test_sharded_slot_boundary_rows(self):
        """Rows landing on shard-local slot 0 (the safe_slot collapse target
        for every foreign row) must still write their window deltas."""
        assert len(jax.devices()) == 8
        cfg = CFG._replace(max_flows=64)  # 8 slots per shard
        rules = [
            ClusterFlowRule(flow_id=i, count=50.0, mode=G) for i in range(20)
        ]
        table, _ = build_rule_table(cfg, rules)
        mesh = make_flow_mesh()
        step_m = make_sharded_decide(cfg, mesh, grouped=True)
        st_m = shard_state(make_state(cfg), mesh)
        st_1 = make_state(cfg)
        tbl = shard_rules(table, mesh)
        # slots 8 and 16 are shard-local slot 0 on shards 1 and 2: every
        # other shard sees them as foreign safe_slot-0 rows that merge with
        # its own (absent) slot-0 segment
        slots = np.asarray([8, 8, 8, 16, 16], np.int32)
        batch = make_batch(cfg, slots)
        now = 20_000
        for _ in range(2):
            st_m, v_m = step_m(st_m, tbl, batch, now)
            st_1, v_1 = decide(cfg, st_1, table, batch, now, grouped=True)
            now += 30
        _assert_trees_equal(v_m, v_1, "boundary verdicts")
        _assert_trees_equal(jax.device_get(st_m), st_1, "boundary state")
        # and the deltas actually landed (3 + 2 PASS_REQUESTs per step)
        flow = jax.device_get(st_m.flow.counts)
        assert flow[8, :, 1].sum() == 6 and flow[16, :, 1].sum() == 4


class TestBreakerParity:
    """The breaker plane through the forks: CLOSED→OPEN trips, retry-after
    verdicts, the HALF_OPEN single-probe election and the transition
    scatters come back bit-identical on the served fast path and the
    general path. Outcome reports go through the outcome step applied to
    each side's state copy, so any divergence is the decide fork's alone."""

    def _build_with_breakers(self, config):
        table, index = build_rule_table(
            config, _mixed_rules(), ns_max_qps=30_000.0,
            connected={"default": 3, "tight": 2},
            degrade_rules=[
                DegradeRule(1, DegradeStrategy.ERROR_RATIO, threshold=0.2,
                            min_request_amount=5, stat_interval_ms=1000,
                            recovery_timeout_ms=300),
                DegradeRule(4, DegradeStrategy.SLOW_REQUEST_RATIO,
                            threshold=0.3, slow_rt_ms=40,
                            min_request_amount=5, stat_interval_ms=1000,
                            recovery_timeout_ms=400, namespace="default"),
                DegradeRule(6, DegradeStrategy.ERROR_COUNT, threshold=3.0,
                            min_request_amount=1, stat_interval_ms=800,
                            recovery_timeout_ms=350, namespace="tight"),
            ],
        )
        return table, index

    def _report(self, ostep, table, state, slots, rts, excs, now):
        k = len(slots)
        return ostep(
            state, jnp.asarray(slots, jnp.int32),
            jnp.asarray(rts, jnp.int32), jnp.asarray(excs, jnp.int32),
            jnp.ones((k,), bool), jnp.int32(now),
            table.br_strategy, table.br_slow_rt_ms,
        )

    @pytest.mark.parametrize("seed", range(2))
    def test_breaker_stream_parity(self, seed):
        table, _ = self._build_with_breakers(CFG)
        ostep = outcome_step_donating(CFG)
        st_f, st_g = make_state(CFG), make_state(CFG)
        rng = np.random.default_rng(0xBEA + seed)
        now = 10_000
        guarded = [1, 4, 6]
        saw_open = False
        for step_i in range(14):
            now += int(rng.integers(40, 260))
            if rng.random() < 0.5:
                k = int(rng.integers(8, 24))
                slots = rng.choice(guarded, size=k).astype(np.int32)
                rts = rng.integers(1, 90, size=k).astype(np.int32)
                excs = (rng.random(k) < 0.5).astype(np.int32)
                st_f = self._report(ostep, table, st_f, slots, rts, excs, now)
                st_g = self._report(ostep, table, st_g, slots, rts, excs, now)
            else:
                n = int(rng.integers(6, 20))
                slots = rng.choice(guarded + [0, 29], size=n).astype(np.int32)
                slots.sort()
                batch = make_batch(CFG, slots)
                st_f, v_f = decide(CFG, st_f, table, batch, now,
                                   grouped=True)
                st_g, v_g = decide(CFG, st_g, table, batch, now)
                _assert_trees_equal(
                    v_f, v_g, f"breaker verdicts seed={seed} step={step_i}"
                )
                saw_open |= bool(
                    (np.asarray(v_f.status)[:n]
                     == int(TokenStatus.DEGRADED)).any()
                )
            _assert_trees_equal(
                st_f, st_g, f"breaker state seed={seed} step={step_i}"
            )
        # the error-heavy stream must actually trip breakers — an
        # all-CLOSED parity run would not cover the transition scatters
        assert saw_open

    def test_half_open_probe_parity(self):
        """Trip flow 1, wait out recovery, then send a grouped batch of 8
        same-flow rows: both paths must elect exactly the first row as the
        probe and stamp identical probe tickets."""
        table, _ = self._build_with_breakers(CFG)
        ostep = outcome_step_donating(CFG)
        st_f, st_g = make_state(CFG), make_state(CFG)
        slots, rts, excs = [1] * 8, [5] * 8, [1] * 8
        st_f = self._report(ostep, table, st_f, slots, rts, excs, 10_000)
        st_g = self._report(ostep, table, st_g, slots, rts, excs, 10_000)

        def both(now, rows):
            nonlocal st_f, st_g
            batch = make_batch(CFG, rows)
            st_f, v_f = decide(CFG, st_f, table, batch, now, grouped=True)
            st_g, v_g = decide(CFG, st_g, table, batch, now)
            _assert_trees_equal(v_f, v_g, f"probe verdicts now={now}")
            _assert_trees_equal(st_f, st_g, f"probe state now={now}")
            return np.asarray(v_f.status)

        status = both(10_050, np.asarray([1], np.int32))  # trips
        assert status[0] == int(TokenStatus.DEGRADED)
        status = both(10_400, np.ones(8, np.int32))  # past recovery: probe
        assert int((status[:8] == int(TokenStatus.OK)).sum()) == 1
        assert status[0] == int(TokenStatus.OK)
        # probe succeeds → CLOSED again, bit-equal columns both sides
        st_f = self._report(ostep, table, st_f, [1], [5], [0], 10_450)
        st_g = self._report(ostep, table, st_g, [1], [5], [0], 10_450)
        assert int(np.asarray(st_f.breaker.state)[1]) == BR_CLOSED
        status = both(10_500, np.ones(4, np.int32))
        assert (status[:4] == int(TokenStatus.OK)).all()

    @pytest.mark.parametrize("depth", [2, 4])
    def test_fused_breaker_scan_parity(self, depth):
        """Breaker columns through the fused ``lax.scan``: an OPEN flow past
        recovery inside a stack of frames: frame 0 elects the probe, every
        later frame sees the live ticket, as the single steps do."""
        table, _ = self._build_with_breakers(CFG)
        ostep = outcome_step_donating(CFG)
        st_f, st_s = make_state(CFG), make_state(CFG)
        slots, rts, excs = [1] * 8, [5] * 8, [1] * 8
        st_f = self._report(ostep, table, st_f, slots, rts, excs, 10_000)
        st_s = self._report(ostep, table, st_s, slots, rts, excs, 10_000)
        trip = make_batch(CFG, np.asarray([1], np.int32))
        st_f, _ = decide(CFG, st_f, table, trip, 10_050, grouped=True)
        st_s, _ = decide(CFG, st_s, table, trip, 10_050, grouped=True)

        frames = [make_batch(CFG, np.ones(6, np.int32)) for _ in range(depth)]
        verdicts = _assert_fused_equals_chain(
            CFG, table, st_f, st_s, frames, 10_400, False,
            f"fused breaker depth={depth}",
        )
        status = verdicts.status[:, :6]
        assert int((status == int(TokenStatus.OK)).sum()) == 1
        assert status[0, 0] == int(TokenStatus.OK)
