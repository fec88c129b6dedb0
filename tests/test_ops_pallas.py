"""Pallas kernels vs. their pure-jax reference implementations.

Runs in interpret mode on the CPU mesh, asked for explicitly (``interpret=True``
on the kernel entry points, the ``pallas_interpret`` fixture for
config-selected kernels); the compiled path is checked on the chip by
``benchmarks/kernel_chip_parity.py``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.engine.param import (
    ParamConfig,
    _param_decide_jax,
    hash_indices,
    make_param_state,
    param_decide,
)
from sentinel_tpu.engine.prefix import segment_prefix_builder
from sentinel_tpu.ops.prefix_pallas import segment_prefix_pallas

pytestmark = pytest.mark.usefixtures("pallas_interpret")


def _ref_prefix(keys, contrib):
    out = np.zeros(len(keys), np.float32)
    for i in range(len(keys)):
        out[i] = sum(contrib[j] for j in range(i) if keys[j] == keys[i])
    return out


class TestPrefixPallas:
    @pytest.mark.parametrize("n", [1, 7, 256, 700])
    def test_matches_reference(self, n):
        rng = np.random.default_rng(n)
        keys = rng.integers(0, max(1, n // 3), size=n).astype(np.int32)
        contrib = rng.integers(0, 5, size=n).astype(np.float32)
        got = np.asarray(
            segment_prefix_pallas(jnp.asarray(keys), jnp.asarray(contrib), interpret=True)
        )
        np.testing.assert_allclose(got, _ref_prefix(keys, contrib), rtol=0, atol=0)

    def test_matches_other_impls(self):
        rng = np.random.default_rng(0)
        n = 300
        keys = jnp.asarray(rng.integers(-5, 5, size=n), jnp.int32)
        contrib = jnp.asarray(rng.random(n, np.float32))
        got = segment_prefix_pallas(keys, contrib, interpret=True)
        for impl in ("matmul", "sort"):
            want = segment_prefix_builder(keys, impl)(contrib)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


class TestCmsPallas:
    CFG_JAX = ParamConfig(max_param_rules=8, depth=2, width=64, bucket_ms=500,
                          n_buckets=2, impl="jax")
    CFG_PALLAS = CFG_JAX._replace(impl="pallas")

    def _batch(self, rng, n, cfg):
        slot = rng.integers(-1, cfg.max_param_rules, size=n).astype(np.int32)
        hashes = rng.integers(0, 2**63 - 1, size=n, dtype=np.int64)
        idx = hash_indices(hashes, cfg.depth, cfg.width)
        acquire = rng.integers(1, 4, size=n).astype(np.int32)
        threshold = rng.integers(1, 20, size=n).astype(np.float32)
        valid = rng.random(n) > 0.1
        return (
            jnp.asarray(slot),
            jnp.asarray(idx),
            jnp.asarray(acquire),
            jnp.asarray(threshold),
            jnp.asarray(valid),
        )

    def test_matches_jax_impl_across_rolls(self):
        rng = np.random.default_rng(42)
        n = 16
        s_jax = make_param_state(self.CFG_JAX)
        s_pl = make_param_state(self.CFG_PALLAS)
        # steps cross bucket boundaries and include an idle gap (full-window
        # staleness) to exercise the roll/replace path
        for now in (100, 400, 600, 1100, 4100, 4200):
            batch = self._batch(rng, n, self.CFG_JAX)
            s_jax, admit_j, est_j = _param_decide_jax(
                self.CFG_JAX, s_jax, *batch, jnp.int32(now)
            )
            s_pl, admit_p, est_p = param_decide(
                self.CFG_PALLAS, s_pl, *batch, jnp.int32(now)
            )
            np.testing.assert_array_equal(np.asarray(admit_j), np.asarray(admit_p))
            np.testing.assert_array_equal(np.asarray(est_j), np.asarray(est_p))
            np.testing.assert_array_equal(
                np.asarray(s_jax.starts), np.asarray(s_pl.starts)
            )
            np.testing.assert_array_equal(
                np.asarray(s_jax.counts), np.asarray(s_pl.counts)
            )

    def test_admission_never_overshoots(self):
        # all requests on one (rule, value): total admitted ≤ threshold
        cfg = self.CFG_PALLAS
        n = 16
        state = make_param_state(cfg)
        idx = jnp.asarray(
            np.tile(hash_indices(np.asarray([7], np.int64), cfg.depth, cfg.width), (n, 1))
        )
        state, admit, _ = param_decide(
            cfg,
            state,
            jnp.full((n,), 3, jnp.int32),
            idx,
            jnp.full((n,), 2, jnp.int32),
            jnp.full((n,), 9.0, jnp.float32),
            jnp.ones((n,), bool),
            jnp.int32(100),
        )
        assert int(np.asarray(admit).sum()) * 2 <= 9
        assert int(np.asarray(admit).sum()) > 0
