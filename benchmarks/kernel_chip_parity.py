"""The three Pallas kernels, compiled on the chip, against their XLA twins.

What ``tests/test_ops_pallas.py`` / ``test_sketch_parity.py`` check under
the interpreter on the CPU, checked where it counts: each kernel is compiled
by Mosaic (never interpreted) at the shapes the serving path hands it, fed
seeded streams whose counts climb past 256 (what a single bf16 MXU pass would
round), and every output and state leaf is compared bitwise with the XLA
implementation run on the same chip.

    python benchmarks/kernel_chip_parity.py

Each kernel ends as ``MATCH`` or as ``REFUSED`` with the compiler's message.
The exit code is non-zero when a kernel is refused or differs: ``auto`` may
resolve to each of them.
"""

from __future__ import annotations

import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SEED = 0


def _equal(label, a, b):
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        raise AssertionError(f"{label}: {len(la)} vs {len(lb)} leaves")
    for i, (x, y) in enumerate(zip(la, lb)):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or not np.array_equal(x, y):
            bad = int((x != y).sum()) if x.shape == y.shape else -1
            raise AssertionError(
                f"{label}: leaf {i} differs ({x.dtype}{x.shape} vs "
                f"{y.dtype}{y.shape}, {bad} cells)"
            )


def prefix_parity():
    import jax.numpy as jnp

    from sentinel_tpu.engine.prefix import segment_prefix_builder
    from sentinel_tpu.ops.prefix_pallas import segment_prefix_pallas

    rng = np.random.default_rng(SEED)
    for n in (64, 256, 700, 1024, 4096, 16384):
        keys = jnp.asarray(np.sort(rng.integers(0, n // 8 + 1, size=n)),
                           jnp.int32)
        # up to 600 tokens a row (past one bf16 pass), totals below 2^24
        contrib = jnp.asarray(rng.integers(0, 600, size=n), jnp.float32)
        got = segment_prefix_pallas(keys, contrib)
        want = segment_prefix_builder(keys, "sort")(contrib)
        _equal(f"prefix n={n}", got, want)
        if float(np.asarray(want).max()) <= 256:
            raise AssertionError("stream never left the bf16-exact range")
    return "n = 64..16384, prefix sums up to ~10^5"


def _sketch_parity(sketch: str):
    import jax.numpy as jnp

    from sentinel_tpu.engine.param import (
        ParamConfig,
        _param_cores,
        hash_indices,
        make_param_state,
    )

    cfg = ParamConfig(sketch=sketch)  # the default geometry: P=256 D=2 W=2048
    cores = _param_cores(sketch)
    rng = np.random.default_rng(SEED)
    peak = 0
    for n in (8, 16, 32, 64):
        st_j, st_p = make_param_state(cfg), make_param_state(cfg)
        now = 1_000
        for step in range(12):
            slot = rng.integers(-1, 4, size=n).astype(np.int32)
            # few distinct values, so cells grow: 12 steps x up to 60 a row
            hashes = rng.integers(0, 6, size=n).astype(np.int64)
            idx = hash_indices(hashes, cfg.depth, cfg.cell_width)
            acquire = rng.integers(1, 61, size=n).astype(np.int32)
            thr = np.where(rng.random(n) < 0.8, 1e6, 300.0).astype(np.float32)
            valid = rng.random(n) < 0.9
            args = (jnp.asarray(slot), jnp.asarray(idx), jnp.asarray(acquire),
                    jnp.asarray(thr), jnp.asarray(valid), jnp.int32(now))
            st_j, ok_j, est_j = cores["jax"](cfg, st_j, *args)
            st_p, ok_p, est_p = cores["pallas"](cfg, st_p, *args)
            _equal(f"{sketch} n={n} step={step} admit/estimate",
                   (ok_j, est_j), (ok_p, est_p))
            _equal(f"{sketch} n={n} step={step} state", st_j, st_p)
            peak = max(peak, int(np.asarray(est_j).max()))
            now += int(rng.choice([40, 300, 700]))  # in-bucket, roll, expiry
    if peak <= 256:
        raise AssertionError("stream never left the bf16-exact range")
    return f"default ParamConfig, N = 8..64, estimates up to {peak}"


def cms_parity():
    return _sketch_parity("cms")


def salsa_parity():
    return _sketch_parity("salsa")


def main() -> None:
    import jax

    from sentinel_tpu.core.compile_cache import ensure_compile_cache
    from sentinel_tpu.ops import KERNEL_BUILD_ERRORS

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"kernel_chip_parity needs a TPU; JAX found {jax.devices()}")
        sys.exit(2)
    ensure_compile_cache()
    print(f"device {dev.device_kind!r}, jax {jax.__version__}", flush=True)
    kernels = (
        ("ops/prefix_pallas.py", prefix_parity),
        ("ops/cms_pallas.py", cms_parity),
        ("ops/salsa_pallas.py", salsa_parity),
    )
    failed = False
    for name, check in kernels:
        try:
            print(f"{name}: MATCH bitwise ({check()})", flush=True)
        except KERNEL_BUILD_ERRORS as e:
            print(f"{name}: REFUSED {type(e).__name__}: {e}", flush=True)
            failed = True
        except AssertionError as e:
            print(f"{name}: MISMATCH {e}", flush=True)
            failed = True
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
