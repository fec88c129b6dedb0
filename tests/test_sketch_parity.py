"""Sketch-variant parity: accuracy vs an exact reference counter.

Tentpole suite for the sketch subsystem (``sentinel_tpu/sketch/``). The
decisive property is ONE-SIDEDNESS — no variant, on any impl, may ever
undercount a key (an undercount admits traffic the rule said to block; an
overcount merely blocks early, the safe direction). On top of that:

- the vectorized ``hash_indices`` is byte-identical to the seed's
  per-depth loop (satellite regression — every historical sketch state
  depends on these indices);
- SALSA at equal HBM bytes holds ≥1.8× the effective key cardinality of
  the plain int32 CMS on the fixed-seed Zipf stream (the paper's memory
  win, measured end to end through the real decide kernels);
- the SF slim twin never undercounts and stays within 2× of the fat
  sketch's error on a stream both can hold (what replication deltas ship
  must still be a safe, useful sketch);
- SALSA merge events surface on the metrics plane
  (``sentinel_sketch_merges_total``) and in ``clusterServerStats``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.engine.param import (
    ParamConfig,
    hash_indices,
    make_param_state,
    param_decide,
)
from sentinel_tpu.sketch import VARIANTS, sketch_stats
from sentinel_tpu.sketch import parity as P
from sentinel_tpu.sketch.slim import SLIM_SALT, slim_query_np

SEED = P.DEFAULT_SEED


def _cfg(sketch, impl="jax", **kw):
    kw.setdefault("max_param_rules", 8)
    kw.setdefault("depth", 2)
    kw.setdefault("width", 512)
    return ParamConfig(sketch=sketch, impl=impl, **kw)


# -- satellite: vectorized hash_indices is byte-identical to the loop ---------
def _hash_indices_loop(value_hashes, depth, width, salt=0):
    """The seed's per-depth host loop, kept verbatim as the reference."""
    mix = np.uint64(0x9E3779B97F4A7C15)
    fin1 = np.uint64(0xBF58476D1CE4E5B9)
    fin2 = np.uint64(0x94D049BB133111EB)
    h = value_hashes.astype(np.uint64)
    out = np.empty((h.shape[0], depth), np.int32)
    with np.errstate(over="ignore"):
        for d in range(depth):
            x = h + np.uint64(salt + d + 1) * mix
            x = (x ^ (x >> np.uint64(30))) * fin1
            x = (x ^ (x >> np.uint64(27))) * fin2
            x = x ^ (x >> np.uint64(31))
            out[:, d] = (x % np.uint64(width)).astype(np.int32)
    return out


@pytest.mark.parametrize("depth,width,salt", [
    (1, 16, 0), (2, 2048, 0), (4, 4096, 0), (2, 256, SLIM_SALT),
])
def test_hash_indices_vectorized_matches_loop(depth, width, salt):
    rng = np.random.default_rng(SEED)
    h = rng.integers(-2 ** 63, 2 ** 63 - 1, size=4096, dtype=np.int64)
    h[:3] = (0, -1, 2 ** 63 - 1)  # edge values
    np.testing.assert_array_equal(
        hash_indices(h, depth, width, salt=salt),
        _hash_indices_loop(h, depth, width, salt=salt),
    )


# -- one-sidedness: no variant ever undercounts -------------------------------
@pytest.mark.parametrize("sketch", VARIANTS)
def test_no_undercount_jax(sketch):
    rep = P.stream_report(
        _cfg(sketch), n_keys=256, n_events=8192, seed=SEED
    )
    assert rep["undercounts"] == 0
    assert rep["slim"]["undercounts"] == 0


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("sketch", VARIANTS)
def test_no_undercount_pallas_interpret(sketch):
    # interpret mode is slow — a small stream still drives the whole
    # kernel (roll, gather, prefix admission, routed update, merge)
    rep = P.stream_report(
        _cfg(sketch, impl="pallas", width=128),
        n_keys=64, n_events=1024, batch=256, seed=SEED, with_slim=False,
    )
    assert rep["undercounts"] == 0


def test_salsa_saturation_merge_never_undercounts():
    """Hammer few keys hard enough to saturate int16 cells: the merge path
    (not just cold cells) must keep the one-sided guarantee."""
    cfg = _cfg("salsa", width=16)
    rep = P.stream_report(
        cfg, n_keys=8, n_events=4096, acquire=64, seed=SEED,
        with_slim=False,
    )
    assert rep["undercounts"] == 0
    assert rep["errCdf"]["max"] >= 0


# -- SALSA memory win ---------------------------------------------------------
@pytest.mark.slow
def test_salsa_effective_cardinality_gain():
    """At equal HBM bytes (int32 width-W vs int16 width-2W), SALSA must
    hold ≥1.8× the key cardinality within the p90 error budget on the
    fixed-seed Zipf stream — the acceptance gate of the sketch PR."""
    base = dict(width=128, depth=2, max_param_rules=4)
    k_cms = P.effective_cardinality(ParamConfig(sketch="cms", impl="jax",
                                                **base))
    k_salsa = P.effective_cardinality(ParamConfig(sketch="salsa", impl="jax",
                                                  **base))
    assert k_salsa / k_cms >= 1.8, (k_cms, k_salsa)


# -- SF slim twin -------------------------------------------------------------
def test_slim_error_within_2x_of_fat():
    """On a stream the slim geometry can hold, the twin's p90 overestimate
    stays within 2× of the fat sketch's (plus a 2-count absolute floor so
    a near-exact fat run can't make the gate vacuous)."""
    cfg = _cfg("cms", width=512, slim_depth=2, slim_width=256)
    rep = P.stream_report(cfg, n_keys=128, n_events=4096, seed=SEED)
    fat_p90 = rep["errCdf"]["p90"]
    slim_p90 = rep["slim"]["errCdf"]["p90"]
    assert rep["slim"]["undercounts"] == 0
    assert slim_p90 <= max(2.0 * fat_p90, 2.0), (fat_p90, slim_p90)


def test_slim_disabled_matches_enabled_fat_bitwise():
    """The twin composes AROUND the fat core: maintaining it must not
    change one bit of the fat sketch."""
    cfg_on = _cfg("cms", slim_depth=2, slim_width=256)
    cfg_off = _cfg("cms", slim_depth=0, slim_width=0)
    hashes, _ = P.zipf_stream(64, 2048, seed=SEED)
    s_on = P.run_stream(cfg_on, hashes)
    s_off = P.run_stream(cfg_off, hashes)
    np.testing.assert_array_equal(
        np.asarray(s_on.counts), np.asarray(s_off.counts)
    )


# -- merge counters reach the metrics plane -----------------------------------
def test_salsa_merges_counted_and_rendered():
    cfg = _cfg("salsa", width=16)
    hashes, _ = P.zipf_stream(8, 2048, seed=SEED)
    state = P.run_stream(cfg, hashes, acquire=64, maintain_slim=False)
    stats = sketch_stats(cfg, state)
    assert stats["variant"] == "salsa"
    assert stats["mergesTotal"] > 0
    assert stats["mergesBySlot"].get(0, 0) == stats["mergesTotal"]
    assert stats["fatBytes"] == np.asarray(state.counts).nbytes

    from sentinel_tpu.metrics.server import (
        reset_server_metrics_for_tests,
        server_metrics,
    )

    sm = server_metrics()
    try:
        sm.register_sketch_provider(lambda: stats)
        body = sm.render()
        assert (
            f'sentinel_sketch_merges_total{{slot="0"}} '
            f'{stats["mergesTotal"]}'
        ) in body
        assert "sentinel_sketch_fat_bytes_total" in body
        assert "sentinel_sketch_slim_bytes_total" in body
        assert sm.snapshot()["sketch"]["mergesTotal"] == stats["mergesTotal"]
    finally:
        reset_server_metrics_for_tests()


def test_sketch_provider_survives_dead_service():
    """A provider whose service died must yield {} (and never break a
    scrape), exactly like a dead gauge reader."""
    from sentinel_tpu.metrics.server import (
        reset_server_metrics_for_tests,
        server_metrics,
    )

    sm = server_metrics()
    try:
        sm.register_sketch_provider(lambda: (_ for _ in ()).throw(
            RuntimeError("service gone")
        ))
        assert sm.sketch_stats() == {}
        assert "sentinel_sketch_merges_total" in sm.render()
    finally:
        reset_server_metrics_for_tests()


# -- impl parity: both kernels, same math -------------------------------------
@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("sketch", VARIANTS)
def test_jax_and_pallas_agree_bitwise(sketch):
    cfg_j = _cfg(sketch, impl="jax", width=128)
    cfg_p = _cfg(sketch, impl="pallas", width=128)
    hashes, _ = P.zipf_stream(32, 512, seed=SEED)
    s_j = P.run_stream(cfg_j, hashes, batch=256, maintain_slim=False)
    s_p = P.run_stream(cfg_p, hashes, batch=256, maintain_slim=False)
    np.testing.assert_array_equal(
        np.asarray(s_j.counts), np.asarray(s_p.counts)
    )
    np.testing.assert_array_equal(
        np.asarray(s_j.merges), np.asarray(s_p.merges)
    )
