"""The hot-parameter lane on the chip, before any cell: exactness, then the
step alone at a deployment's size.

    python3 benchmarks/param_chip_check.py [--config cellbench/configs/hot-param-1k.json]

1. The comparisons of ``tests/test_param_batch.py`` (every scenario of the
   batched service against the plain reference, verdict for verdict; a batch
   against single calls) on the TPU backend, at the tests' small geometry,
   with ``impl="jax"`` and, where Mosaic takes the kernel, ``impl="pallas"``.
2. The serve step of every serve bucket of the configuration, on a sketch of
   its own size: what ``impl="auto"`` resolves to there and why, milliseconds
   a step (chained on the donated state, one blocking read at the end), the
   device's time a step and that of the ``param_commit`` scope's operations
   from a short profile (PR 39), and the device's peak memory.

Exits 2 without a TPU, 1 on a mismatch. Its times are of the step alone, one
thread, nothing else on the host: not a cell's.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]


def exactness() -> int:
    import numpy as np

    import test_param_batch as T
    from sentinel_tpu.core import clock as clock_mod
    from sentinel_tpu.core.clock import ManualClock

    bad = 0
    clock = ManualClock()
    prev = clock_mod.set_clock(clock)
    try:
        for impl in ("jax", "pallas"):
            T.PCFG = T.PCFG._replace(impl=impl)
            try:
                svc = T.make_service()
                svc.param_impl()
                for name, requests in sorted(T.SCENARIOS.items()):
                    clock.advance(2_000)
                    ref = T.Reference(T.RULES)
                    t = svc._engine_now()
                    got = T.ask(svc, requests)
                    want = [ref.decide(t, *r) for r in requests]
                    wrong = int((np.array(got) != np.array(want)).sum())
                    bad += wrong
                    print(f"exact[{impl}] {name}: {len(requests)} requests, "
                          f"{wrong} mismatches", flush=True)
                svc.close()
            except Exception as e:  # the kernel may be refused: say so
                print(f"exact[{impl}]: not run: {type(e).__name__}: "
                      f"{str(e)[:300]}", flush=True)
                if impl == "jax":
                    raise
    finally:
        clock_mod.set_clock(prev)
    return bad


def _commit_device_ms(profile_dir: str, program: str, hlo: str):
    """``(device ms a run of ``program``, ms a run in the operations of the
    ``param_commit`` scope, [[op, us a run]] of the six longest)`` from the
    profiler's trace; the scope of an operation is read off the compiled
    program's own text (``op_name`` in its metadata). ``(None, None, [])``
    where the trace holds no TPU plane."""
    import re

    from cellbench import trace as T

    scope = {m.group(1): m.group(2) for m in re.finditer(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", hlo, re.M)}
    try:
        planes = T.Trace(T.find_xplane(profile_dir)).devices
    except (FileNotFoundError, ValueError):
        planes = {}
    for plane in planes.values():
        names, start, dur = plane["modules"]
        mine = [i for i, n in enumerate(names) if str(n).startswith(program)]
        if not mine:
            continue
        ops = {}
        for name, d in zip(*plane["ops"][::2]):
            name = T.short_name(name)
            if "param_commit" in scope.get(name, ""):
                ops[name] = ops.get(name, 0.0) + d
        runs = len(mine)
        return (float(dur[mine].sum()) / runs / 1e6,
                sum(ops.values()) / runs / 1e6,
                [[op, ns / runs / 1e3] for op, ns in
                 sorted(ops.items(), key=lambda kv: -kv[1])[:6]])
    return None, None, []


def step_alone(config_file: str) -> None:
    import shutil
    import tempfile

    import jax
    import numpy as np

    from sentinel_tpu.engine.param import (
        ROW_THRESHOLD,
        ParamConfig,
        explain_param_impl,
        hash_indices,
        make_param_state,
        make_param_step,
        pack_param_rows,
    )
    from sentinel_tpu.sketch.slim import slim_indices

    with open(config_file, encoding="utf-8") as f:
        spec = json.load(f)
    cfg = ParamConfig(**spec["param"])
    buckets = sorted(spec["serve_buckets"])
    kernel, reason = explain_param_impl(cfg.impl, cfg.sketch, cfg,
                                        buckets[-1])
    print(f"impl {cfg.impl!r} -> {kernel!r}: {reason}", flush=True)
    rng = np.random.default_rng(1)
    state = make_param_state(cfg, flat=True)
    for bucket in buckets:
        step = make_param_step(cfg, bucket, kernel)
        hashes = rng.integers(-2**62, 2**62, bucket)
        packed = pack_param_rows(
            cfg, bucket, rng.integers(0, cfg.max_param_rules, bucket),
            np.ones(bucket, np.int32), np.full(bucket, 5.0),
            hash_indices(hashes, cfg.depth, cfg.cell_width),
            slim_indices(cfg, hashes), 1_000, 1, bucket)
        t0 = time.perf_counter()
        state, verdicts = step(state, packed)
        jax.block_until_ready(verdicts)
        first = time.perf_counter() - t0
        times = []
        for now in (1_100, 1_600):  # inside one bucket; then a stale one
            packed[-1, 0] = now
            t0 = time.perf_counter()
            for _ in range(20):
                state, verdicts = step(state, packed)
            jax.block_until_ready(verdicts)
            times.append((time.perf_counter() - t0) / 20 * 1e3)
        print(f"step b{bucket}: first call {first:.2f} s, {times[0]:.3f} "
              f"ms/step, {times[1]:.3f} ms/step over a bucket boundary; "
              f"blocked {int((np.asarray(verdicts)[0] == 1).sum())} of "
              f"{bucket}", flush=True)
        # 20 steps more under the profiler, with a threshold no row meets:
        # every row is admitted, so every step commits bucket x depth cells
        # (the timed steps above refuse most rows once a value has its 5)
        packed[ROW_THRESHOLD] = np.float32(2.0**30).view(np.int32)
        hlo = step.lower(state, packed).compile().as_text()
        profile = tempfile.mkdtemp(prefix="param_step_")
        try:
            jax.profiler.start_trace(profile)
            for _ in range(20):
                state, verdicts = step(state, packed)
            jax.block_until_ready(verdicts)
            jax.profiler.stop_trace()
            device, commit, ops = _commit_device_ms(
                profile, f"jit_param_decide_b{bucket}", hlo)
        finally:
            shutil.rmtree(profile, ignore_errors=True)
        if device is None:
            print(f"commit b{bucket}: not measured (no TPU plane in the "
                  f"profile)", flush=True)
        else:
            cells = bucket * cfg.depth
            print(f"commit b{bucket}: device {device:.4f} ms/step with every "
                  f"row admitted, of it param_commit {commit:.4f} ms "
                  f"({commit * 1e6 / cells:.0f} ns a cell of {cells}): "
                  + ", ".join(
                      f"{op} {us:.1f} us" for op, us in ops), flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}, "
          f"bytes_in_use {stats.get('bytes_in_use')}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default=os.path.join(
        ROOT, "cellbench", "configs", "hot-param-1k.json"))
    args = ap.parse_args()
    import jax

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    if dev.platform != "tpu":
        print("needs a TPU: no result")
        raise SystemExit(2)
    bad = exactness()
    step_alone(args.config)
    print(f"param_chip_check: {bad} mismatches")
    raise SystemExit(1 if bad else 0)


if __name__ == "__main__":
    main()
