"""Accuracy + cost sweep for the param-sketch variants.

Runs every ``ParamConfig.sketch`` variant through the real decide kernels
on fixed-seed Zipf streams (``sentinel_tpu/sketch/parity.py``) and emits a
BENCH-style artifact: per-key overestimate CDF vs an exact reference,
effective key cardinality at equal HBM bytes (the SALSA memory win),
update/query timings, and the SF slim twin's stats. Both impls are
covered — off the TPU this script asks JAX for the Pallas interpreter
itself (``pltpu.force_tpu_interpret_mode``; nothing in the package picks
interpret mode), so the ``pallas`` streams are kept small there (the
numbers prove semantics, not speed).

``--smoke`` is the CI ``sketch-parity`` gate: exit nonzero unless

- every variant × impl shows ZERO undercounts (the one-sided guarantee);
- the slim twin's p90 error stays within 2× of the fat sketch's;
- SALSA holds ≥1.8× the CMS effective cardinality at equal bytes.

Usage: ``JAX_PLATFORMS=cpu python benchmarks/sketch_bench.py [--smoke]``
Prints ONE JSON line and appends a copy under ``benchmarks/results/``.
"""

from __future__ import annotations

import os as _os
import sys as _sys

_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO not in _sys.path:
    _sys.path.insert(0, _REPO)

import argparse
import contextlib
import json
import os
import time

SMOKE_CARDINALITY_RATIO = 1.8
SMOKE_SLIM_ERR_FACTOR = 2.0
# absolute floor for the slim gate, as a fraction of mean events/key: a
# near-exact fat sketch (SALSA on a cold stream) must not make "2× of
# fat" an impossible zero-error bar for the much smaller slim twin
SMOKE_SLIM_ERR_FLOOR_FRAC = 0.25


def run(smoke: bool = False) -> dict:
    import jax
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    from sentinel_tpu.engine.param import ParamConfig
    from sentinel_tpu.sketch import VARIANTS, sketch_stats
    from sentinel_tpu.sketch.parity import (
        DEFAULT_SEED,
        effective_cardinality,
        key_hashes,
        query_np,
        run_stream,
        stream_report,
        zipf_stream,
    )

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    failures = []
    out = {
        "bench": "sketch",
        "backend": backend,
        "seed": DEFAULT_SEED,
        "smoke": smoke,
        "variants": {},
        "effective_cardinality": {},
        "failures": failures,
    }

    for sketch in VARIANTS:
        for impl in ("jax", "pallas"):
            # off the TPU the pallas variants run under the interpreter,
            # asked for here and orders of magnitude slower than the XLA
            # path — keep their streams small
            small = impl == "pallas" and not on_tpu
            interpreter = (
                pltpu.force_tpu_interpret_mode() if small
                else contextlib.nullcontext()
            )
            with interpreter:
                cfg = ParamConfig(
                    max_param_rules=8,
                    depth=2,
                    width=64 if small else 512,
                    sketch=sketch,
                    impl=impl,
                )
                n_keys, n_events = (48, 1024) if small else (256, 8192)
                with_slim = impl == "jax"  # one slim measurement per variant
                rep = stream_report(
                    cfg,
                    n_keys=n_keys,
                    n_events=n_events,
                    seed=DEFAULT_SEED,
                    batch=256 if small else 512,
                    with_slim=with_slim,
                )
                # timings on a warm jit: feed the identical stream twice, time
                # the second pass; host query timed over every distinct key
                hashes, _ = zipf_stream(n_keys, n_events, seed=DEFAULT_SEED)
                state = run_stream(cfg, hashes, batch=256 if small else 512,
                                   maintain_slim=with_slim)
                t0 = time.perf_counter()
                state = run_stream(cfg, hashes, batch=256 if small else 512,
                                   maintain_slim=with_slim)
                update_ns = (time.perf_counter() - t0) * 1e9 / n_events
                keys = key_hashes(n_keys, DEFAULT_SEED)
                t0 = time.perf_counter()
                query_np(cfg, state, 0, keys, 1_000)
                query_ns = (time.perf_counter() - t0) * 1e9 / n_keys
                rep["updateNsPerEvent"] = round(update_ns, 1)
                rep["hostQueryNsPerKey"] = round(query_ns, 1)
                rep["sketchStats"] = sketch_stats(cfg, state)
            out["variants"][f"{sketch}/{impl}"] = rep

            if rep["undercounts"]:
                failures.append(
                    f"{sketch}/{impl}: {rep['undercounts']} undercounts"
                )
            if with_slim and "slim" in rep:
                if rep["slim"]["undercounts"]:
                    failures.append(
                        f"{sketch}/{impl}: slim twin undercounts "
                        f"({rep['slim']['undercounts']})"
                    )
                fat_p90 = float(rep["errCdf"]["p90"])
                slim_p90 = float(rep["slim"]["errCdf"]["p90"])
                floor = SMOKE_SLIM_ERR_FLOOR_FRAC * n_events / n_keys
                if slim_p90 > max(SMOKE_SLIM_ERR_FACTOR * fat_p90, floor):
                    failures.append(
                        f"{sketch}/{impl}: slim p90 {slim_p90:.1f} over "
                        f"2x fat p90 {fat_p90:.1f}"
                    )

    # effective cardinality at equal HBM bytes: int32 width-W CMS vs int16
    # width-2W SALSA are byte-identical, so the ratio is the memory win
    card_base = dict(max_param_rules=4, depth=2, width=128, impl="jax")
    for sketch in VARIANTS:
        out["effective_cardinality"][sketch] = round(
            effective_cardinality(ParamConfig(sketch=sketch, **card_base)), 2
        )
    k_cms = out["effective_cardinality"]["cms"]
    k_salsa = out["effective_cardinality"]["salsa"]
    ratio = k_salsa / max(k_cms, 1e-9)
    out["effective_cardinality"]["ratio"] = round(ratio, 2)
    if ratio < SMOKE_CARDINALITY_RATIO:
        failures.append(
            f"salsa effective cardinality only {ratio:.2f}x cms "
            f"(need >= {SMOKE_CARDINALITY_RATIO}x)"
        )
    # numpy scalars json-serializable
    return json.loads(json.dumps(out, default=float))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="gate on the CI invariants; exit 1 on violation")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend")
    args = ap.parse_args()
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    t0 = time.time()
    doc = run(smoke=args.smoke)
    doc["wall_s"] = round(time.time() - t0, 1)
    print(json.dumps(doc))
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
    os.makedirs(d, exist_ok=True)
    path = os.path.join(
        d, f"sketch-{time.strftime('%Y%m%d-%H%M%S')}.json"
    )
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    if args.smoke and doc["failures"]:
        print(f"SKETCH BENCH FAILED: {doc['failures']}", file=_sys.stderr)
        _sys.exit(1)


if __name__ == "__main__":
    main()
