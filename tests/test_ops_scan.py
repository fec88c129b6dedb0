"""Blocked (matmul/reduce) scan ops vs numpy oracles.

The matmul/reduce formulation is what a chip process runs; off the TPU the
same functions hand over to XLA's own cumsum/cummax. The suite runs on the
CPU, so the ``tpu_branch`` fixture patches the backend query and the chip's
code is what gets checked here (its MXU precision is checked on the chip,
by ``chip_smoke.py``'s exact counts).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.ops.scan_mm import blocked_cummax, blocked_cumsum


@pytest.fixture
def tpu_branch(monkeypatch):
    """Take the ``jax.default_backend() == "tpu"`` side (read at trace time
    by ops/scan_mm.py and engine/decide.py); the rest of this file and the
    engine tests cover the other one."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.usefixtures("tpu_branch")
class TestTpuBranch:
    def test_cumsum_integer_counts_past_2_16(self):
        rng = np.random.default_rng(0)
        x = rng.integers(0, 60, 5000).astype(np.float32)  # total ~147k
        want = np.cumsum(x.astype(np.int64))
        assert want[-1] > 2**16
        got = np.asarray(blocked_cumsum(jnp.asarray(x)))
        np.testing.assert_array_equal(got, want.astype(np.float32))
        got2 = np.asarray(
            blocked_cumsum(jnp.asarray(np.stack([x, x[::-1]], axis=1)))
        )
        np.testing.assert_array_equal(got2[:, 0], want.astype(np.float32))

    def test_cummax_matches_lax(self):
        rng = np.random.default_rng(1)
        x = rng.integers(-1, 200_000, 3000).astype(np.float32)
        got = np.asarray(blocked_cummax(jnp.asarray(x)))
        np.testing.assert_array_equal(got, np.maximum.accumulate(x))

    def test_ns_guard_precise_arm(self):
        """A namespace whose budget boundary falls inside the batch: exactly
        the overflow is refused, and the per-namespace totals are exact —
        by the one-hot einsum on the chip, by scatter-add elsewhere."""
        from sentinel_tpu.engine import (
            ClusterFlowRule,
            EngineConfig,
            TokenStatus,
            build_rule_table,
            make_batch,
            make_state,
        )
        from sentinel_tpu.engine.decide import _decide_core
        from sentinel_tpu.engine.rules import ThresholdMode

        cfg = EngineConfig(max_flows=64, max_namespaces=4, batch_size=512)
        rules = [
            ClusterFlowRule(i, 1e6, ThresholdMode.GLOBAL, f"ns{i % 2}")
            for i in range(8)
        ]
        table, index = build_rule_table(cfg, rules, ns_max_qps=300.0)
        slots = np.sort(
            [index.lookup(i) for i in np.arange(500) % 8]
        ).astype(np.int32)
        step = jax.jit(
            lambda st, now: _decide_core(
                cfg, st, table, make_batch(cfg, slots), now,
                grouped=True, uniform=True,
            )
        )
        state, v = step(make_state(cfg), jnp.int32(10_000))
        status = np.asarray(v.status)[:500]
        # 250 rows per namespace against a 300/s guard over a 1 s window
        assert int((status == TokenStatus.OK).sum()) == 500
        state, v = step(state, jnp.int32(10_050))
        status = np.asarray(v.status)[:500]
        assert int((status == TokenStatus.OK).sum()) == 100
        assert int((status == TokenStatus.TOO_MANY_REQUEST).sum()) == 400
        # the guard window counted every ns-admitted arrival, no more
        assert int(np.asarray(state.ns.counts).sum()) == 600


class TestBlockedCumsum:
    @pytest.mark.parametrize("n", [1, 5, 128, 129, 1000, 4096])
    def test_1d(self, n):
        rng = np.random.default_rng(n)
        x = rng.integers(0, 100, n).astype(np.float32)
        got = np.asarray(blocked_cumsum(jnp.asarray(x)))
        np.testing.assert_allclose(got, np.cumsum(x), rtol=0, atol=0)

    @pytest.mark.parametrize("n,k", [(7, 3), (128, 64), (300, 5)])
    def test_2d(self, n, k):
        rng = np.random.default_rng(n * k)
        x = rng.integers(0, 50, (n, k)).astype(np.float32)
        got = np.asarray(blocked_cumsum(jnp.asarray(x)))
        np.testing.assert_allclose(got, np.cumsum(x, axis=0), rtol=0, atol=0)

    def test_small_block(self):
        x = np.arange(20, dtype=np.float32)
        got = np.asarray(blocked_cumsum(jnp.asarray(x), block=8))
        np.testing.assert_allclose(got, np.cumsum(x))


class TestBlockedCummax:
    @pytest.mark.parametrize("n", [1, 5, 128, 129, 1000, 4096])
    def test_1d(self, n):
        rng = np.random.default_rng(n)
        x = rng.normal(size=n).astype(np.float32) * 100
        got = np.asarray(blocked_cummax(jnp.asarray(x)))
        np.testing.assert_allclose(got, np.maximum.accumulate(x))

    def test_negative_heads(self):
        # the segment-rebase caller feeds -1 for non-head rows
        x = np.array([-1, 3, -1, -1, 7, -1, 2], dtype=np.float32)
        got = np.asarray(blocked_cummax(jnp.asarray(x), block=4))
        np.testing.assert_allclose(got, np.maximum.accumulate(x))
