"""Bitwise parity of the one-HBM-traversal decide megakernel.

The contract (``ops/decide_pallas.py``): for grouped batches the Pallas
step is a drop-in twin of the XLA ``_decide_core`` — every verdict field
and every state leaf comes back *bit-identical*, across mixed control
behaviors (DEFAULT / WARM_UP / RATE_LIMITER / WARM_UP_RATE_LIMITER),
prioritized occupy borrows, namespace-guard boundary crossings, window
rolls and idle gaps, the fused ``lax.scan`` depth, and the 8-virtual-device
sharded step. The kernel runs under the ``pallas_interpret`` fixture here
(same twin discipline as ``tests/test_ops_pallas.py``); compiled parity on
the chip is ``benchmarks/kernel_chip_parity.py``.

Equality is ``==`` on raw arrays, never ``allclose``: any divergence is a
semantics drift in one of the twins, not float noise.
"""

import os

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
    RequestBatch,
    _decide_core,
    decide_fused_donating,
    pack_batch,
    resolve_decide_impl,
    unpack_verdicts,
)
from sentinel_tpu.engine import DegradeRule, DegradeStrategy, TokenStatus
from sentinel_tpu.engine.outcome import outcome_step_donating
from sentinel_tpu.engine.rules import ControlBehavior, ThresholdMode
from sentinel_tpu.engine.state import BR_CLOSED
from sentinel_tpu.ops.decide_pallas import MAX_BATCH, decide_core_pallas
from sentinel_tpu.parallel import (
    make_flow_mesh,
    make_sharded_decide,
    shard_rules,
    shard_state,
)

pytestmark = pytest.mark.usefixtures("pallas_interpret")

G = ThresholdMode.GLOBAL
CB = ControlBehavior

CFG_X = EngineConfig(
    max_flows=32, max_namespaces=4, batch_size=64, decide_impl="xla"
)
CFG_P = CFG_X._replace(decide_impl="pallas")


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


class TestMegakernelParity:
    @pytest.mark.parametrize("uniform", [False, True])
    @pytest.mark.parametrize("seed", range(3))
    def test_stream_parity_single_shard(self, seed, uniform):
        table, _ = _build(CFG_X)
        rng = np.random.default_rng(seed)
        st_x, st_p = make_state(CFG_X), make_state(CFG_P)
        for step_i, (now, batch) in enumerate(
            _stream(rng, CFG_X, steps=10, uniform=uniform)
        ):
            st_x, v_x = decide(
                CFG_X, st_x, table, batch, now, grouped=True, uniform=uniform
            )
            st_p, v_p = decide(
                CFG_P, st_p, table, batch, now, grouped=True, uniform=uniform
            )
            _assert_trees_equal(
                v_x, v_p, f"verdicts seed={seed} step={step_i}"
            )
            _assert_trees_equal(
                st_x, st_p, f"state seed={seed} step={step_i}"
            )

    def test_prioritized_occupy_parity(self):
        """Saturate a flow so prioritized rows reach the occupy/borrow arm
        (SHOULD_WAIT + future-window charge) in both backends."""
        table, _ = _build(CFG_X)
        st_x, st_p = make_state(CFG_X), make_state(CFG_P)

        def both(batch, now):
            nonlocal st_x, st_p
            st_x, v_x = decide(CFG_X, st_x, table, batch, now, grouped=True)
            st_p, v_p = decide(CFG_P, st_p, table, batch, now, grouped=True)
            _assert_trees_equal(v_x, v_p, f"occupy verdicts now={now}")
            _assert_trees_equal(st_x, st_p, f"occupy state now={now}")
            return v_x

        # fill flow 0 (count 6 → window budget 6) at the window's start …
        both(make_batch(CFG_X, np.zeros(6, np.int32)), 50_000)
        # … then near its end: passed=6 blocks everyone, but those 6 tokens
        # expire by the next bucket, so prioritized rows can borrow ahead
        prio = np.ones(4, bool)
        v = both(
            make_batch(CFG_X, np.zeros(4, np.int32), np.ones(4, np.int32),
                       prio),
            50_950,
        )
        waits = np.asarray(v.wait_ms)[:4]
        assert (waits > 0).any()  # the borrow arm actually fired
        # matured borrows fold into the PASS read of the next window
        both(make_batch(CFG_X, np.zeros(8, np.int32)), 51_010)

    def test_fused_scan_parity(self):
        depth = 3
        table, _ = _build(CFG_X)
        step_x = decide_fused_donating(CFG_X, depth, grouped=True)
        step_p = decide_fused_donating(CFG_P, depth, grouped=True)
        rng = np.random.default_rng(7)
        frames = list(_stream(rng, CFG_X, steps=depth, uniform=False))
        now = frames[0][0]
        batches = jax.tree.map(
            lambda *xs: np.stack(xs), *[b for _, b in frames]
        )
        packed = pack_batch(batches, now)
        st_x, v_x = step_x(make_state(CFG_X), table, packed)
        st_p, v_p = step_p(make_state(CFG_P), table, packed)
        _assert_trees_equal(v_x, v_p, "fused verdicts")
        _assert_trees_equal(st_x, st_p, "fused state")

    def test_sharded_parity_8dev(self):
        assert len(jax.devices()) == 8, "conftest provides 8 virtual devices"
        cfg_x = CFG_X._replace(max_flows=64)
        cfg_p = cfg_x._replace(decide_impl="pallas")
        table, _ = _build(cfg_x)
        mesh = make_flow_mesh()
        step_x = make_sharded_decide(cfg_x, mesh, grouped=True)
        step_p = make_sharded_decide(cfg_p, mesh, grouped=True)
        st_x = shard_state(make_state(cfg_x), mesh)
        st_p = shard_state(make_state(cfg_p), mesh)
        tbl = shard_rules(table, mesh)
        rng = np.random.default_rng(11)
        for step_i, (now, batch) in enumerate(
            _stream(rng, cfg_x, steps=6, uniform=False)
        ):
            st_x, v_x = step_x(st_x, tbl, batch, now)
            st_p, v_p = step_p(st_p, tbl, batch, now)
            _assert_trees_equal(v_x, v_p, f"sharded verdicts step={step_i}")
            _assert_trees_equal(
                jax.device_get(st_x), jax.device_get(st_p),
                f"sharded state step={step_i}",
            )

    def test_sharded_slot_boundary_rows(self):
        """Rows landing on shard-local slot 0 (the safe_slot collapse target
        for every foreign row) must still write their window deltas — the
        merged-segment write-mask case."""
        assert len(jax.devices()) == 8
        cfg_x = CFG_X._replace(max_flows=64)  # 8 slots per shard
        cfg_p = cfg_x._replace(decide_impl="pallas")
        rules = [
            ClusterFlowRule(flow_id=i, count=50.0, mode=G) for i in range(20)
        ]
        table, _ = build_rule_table(cfg_x, rules)
        mesh = make_flow_mesh()
        step_x = make_sharded_decide(cfg_x, mesh, grouped=True)
        step_p = make_sharded_decide(cfg_p, mesh, grouped=True)
        st_x = shard_state(make_state(cfg_x), mesh)
        st_p = shard_state(make_state(cfg_p), mesh)
        tbl = shard_rules(table, mesh)
        # slots 8 and 16 are shard-local slot 0 on shards 1 and 2: every
        # other shard sees them as foreign safe_slot-0 rows that merge with
        # its own (absent) slot-0 segment
        slots = np.asarray([8, 8, 8, 16, 16], np.int32)
        batch = make_batch(cfg_x, slots)
        now = 20_000
        for _ in range(2):
            st_x, v_x = step_x(st_x, tbl, batch, now)
            st_p, v_p = step_p(st_p, tbl, batch, now)
            now += 30
        _assert_trees_equal(v_x, v_p, "boundary verdicts")
        _assert_trees_equal(
            jax.device_get(st_x), jax.device_get(st_p), "boundary state"
        )
        # and the deltas actually landed (3 + 2 PASS_REQUESTs per step)
        flow = jax.device_get(st_x.flow.counts)
        assert flow[8, :, 1].sum() == 6 and flow[16, :, 1].sum() == 4


class TestBreakerParity:
    """The breaker plane inside the megakernel: CLOSED→OPEN trips,
    retry-after verdicts, the HALF_OPEN single-probe election, and the
    transition scatters must come back bit-identical to the XLA core.
    Outcome reports go through the (backend-independent) outcome step
    applied to each backend's state copy, so any divergence is the decide
    twin's fault alone."""

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
        table, _ = self._build_with_breakers(CFG_X)
        ostep = outcome_step_donating(CFG_X)
        st_x, st_p = make_state(CFG_X), make_state(CFG_P)
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
                st_x = self._report(ostep, table, st_x, slots, rts, excs, now)
                st_p = self._report(ostep, table, st_p, slots, rts, excs, now)
            else:
                n = int(rng.integers(6, 20))
                slots = rng.choice(guarded + [0, 29], size=n).astype(np.int32)
                slots.sort()
                batch = make_batch(CFG_X, slots)
                st_x, v_x = decide(CFG_X, st_x, table, batch, now,
                                   grouped=True)
                st_p, v_p = decide(CFG_P, st_p, table, batch, now,
                                   grouped=True)
                _assert_trees_equal(
                    v_x, v_p, f"breaker verdicts seed={seed} step={step_i}"
                )
                saw_open |= bool(
                    (np.asarray(v_x.status)[:n]
                     == int(TokenStatus.DEGRADED)).any()
                )
            _assert_trees_equal(
                st_x, st_p, f"breaker state seed={seed} step={step_i}"
            )
        # the error-heavy stream must actually trip breakers — an
        # all-CLOSED parity run would not cover the transition scatters
        assert saw_open

    def test_half_open_probe_parity(self):
        """Trip flow 1, wait out recovery, then send a grouped batch of 8
        same-flow rows: both backends must elect exactly the first row as
        the probe and stamp identical probe tickets."""
        table, _ = self._build_with_breakers(CFG_X)
        ostep = outcome_step_donating(CFG_X)
        st_x, st_p = make_state(CFG_X), make_state(CFG_P)
        slots, rts, excs = [1] * 8, [5] * 8, [1] * 8
        st_x = self._report(ostep, table, st_x, slots, rts, excs, 10_000)
        st_p = self._report(ostep, table, st_p, slots, rts, excs, 10_000)

        def both(now, rows):
            nonlocal st_x, st_p
            batch = make_batch(CFG_X, rows)
            st_x, v_x = decide(CFG_X, st_x, table, batch, now, grouped=True)
            st_p, v_p = decide(CFG_P, st_p, table, batch, now, grouped=True)
            _assert_trees_equal(v_x, v_p, f"probe verdicts now={now}")
            _assert_trees_equal(st_x, st_p, f"probe state now={now}")
            return np.asarray(v_x.status)

        status = both(10_050, np.asarray([1], np.int32))  # trips
        assert status[0] == int(TokenStatus.DEGRADED)
        status = both(10_400, np.ones(8, np.int32))  # past recovery: probe
        assert int((status[:8] == int(TokenStatus.OK)).sum()) == 1
        assert status[0] == int(TokenStatus.OK)
        # probe succeeds → CLOSED again, bit-equal columns both sides
        st_x = self._report(ostep, table, st_x, [1], [5], [0], 10_450)
        st_p = self._report(ostep, table, st_p, [1], [5], [0], 10_450)
        assert int(np.asarray(st_x.breaker.state)[1]) == BR_CLOSED
        status = both(10_500, np.ones(4, np.int32))
        assert (status[:4] == int(TokenStatus.OK)).all()

    def test_fused_breaker_scan_parity(self):
        """Breaker columns through the fused ``lax.scan``: an OPEN flow past
        recovery inside a 2-deep stack — frame 0 elects, frame 1 sees the
        live ticket, identically in both backends."""
        depth = 2
        table, _ = self._build_with_breakers(CFG_X)
        ostep = outcome_step_donating(CFG_X)
        st_x, st_p = make_state(CFG_X), make_state(CFG_P)
        slots, rts, excs = [1] * 8, [5] * 8, [1] * 8
        st_x = self._report(ostep, table, st_x, slots, rts, excs, 10_000)
        st_p = self._report(ostep, table, st_p, slots, rts, excs, 10_000)
        trip = make_batch(CFG_X, np.asarray([1], np.int32))
        st_x, _ = decide(CFG_X, st_x, table, trip, 10_050, grouped=True)
        st_p, _ = decide(CFG_P, st_p, table, trip, 10_050, grouped=True)

        step_x = decide_fused_donating(CFG_X, depth, grouped=True)
        step_p = decide_fused_donating(CFG_P, depth, grouped=True)
        frames = [make_batch(CFG_X, np.ones(6, np.int32)) for _ in range(2)]
        batches = jax.tree.map(lambda *xs: np.stack(xs), *frames)
        packed = pack_batch(batches, 10_400)
        st_x, v_x = step_x(st_x, table, packed)
        st_p, v_p = step_p(st_p, table, packed)
        _assert_trees_equal(v_x, v_p, "fused breaker verdicts")
        _assert_trees_equal(st_x, st_p, "fused breaker state")
        status = unpack_verdicts(v_x).status[:, :6]
        assert int((status == int(TokenStatus.OK)).sum()) == 1
        assert status[0, 0] == int(TokenStatus.OK)


class TestBackendSelection:
    def test_resolve_explicit(self):
        assert resolve_decide_impl("xla") == "xla"
        assert resolve_decide_impl("pallas") == "pallas"
        with pytest.raises(ValueError):
            resolve_decide_impl("mosaic")

    def test_auto_off_tpu_picks_xla(self, monkeypatch):
        monkeypatch.delenv("SENTINEL_DECIDE_IMPL", raising=False)
        if jax.default_backend() != "tpu":
            assert resolve_decide_impl("auto") == "xla"

    def test_env_var_overrides_auto(self, monkeypatch):
        monkeypatch.setenv("SENTINEL_DECIDE_IMPL", "pallas")
        assert resolve_decide_impl("auto") == "pallas"

    def test_non_grouped_batches_use_xla(self):
        from sentinel_tpu.engine.decide import _core_for

        assert _core_for(CFG_P, grouped=False) is _decide_core
        assert _core_for(CFG_P, grouped=True) is decide_core_pallas
        assert _core_for(CFG_X, grouped=True) is _decide_core

    def test_oversized_batch_core_is_chosen_in_the_open(self):
        """Above the kernel's VMEM cap a forced "pallas" config builds the
        XLA core, and says so: the choice is made in ``decide_core_name``
        (where the service can report it per bucket), not inside the
        Pallas core, which refuses the shape outright."""
        from sentinel_tpu.engine.decide import _core_for, decide_core_name

        cfg = CFG_P._replace(batch_size=MAX_BATCH + 64)
        assert decide_core_name(cfg, grouped=True) == "xla"
        assert _core_for(cfg, grouped=True) is _decide_core
        assert decide_core_name(
            CFG_P._replace(batch_size=MAX_BATCH), grouped=True
        ) == "pallas"
        table, _ = build_rule_table(cfg, [])
        with pytest.raises(ValueError, match="at most"):
            decide_core_pallas(
                cfg, make_state(cfg), table, make_batch(cfg, [0]),
                jnp.int32(5_000), grouped=True,
            )
