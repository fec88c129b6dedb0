"""The concurrency plane on the chip, before any cell: exactness, then the
step alone at a deployment's size, with its time per scope.

    python3 benchmarks/concurrent_chip_check.py \
        [--config cellbench/configs/concurrent-mesh-100k.json]
    JAX_PLATFORMS=cpu python3 benchmarks/concurrent_chip_check.py --compile

1. The service's batched entry against the plain reference
   (``cellbench/families/concurrent_reference.py``) on the TPU backend: seeded
   interleavings of acquires, releases, duplicate and stale ids, expiry in
   between, on a table of the configuration's own size (100k rule slots, a
   ring of 1,048,576 tokens), every status and ``remaining`` row for row,
   ``held`` equal to the reference's after every step.
2. The step of every serve bucket alone on that table, a full bucket of
   acquire rows and as many releases of the step before: milliseconds a step
   (chained on the donated state, one blocking read at the end), and from a
   short profile the device's time a step and in each of the step's four
   scopes (``concurrent_release``, ``_expire``, ``_admit``, ``_issue``), with
   the longest operations; the device's peak memory.

``--compile`` needs no chip: it compiles every serve bucket's step for a
described v5e at the configuration's size (what the chip's compiler would
refuse, it refuses here) and prints each program's memory. Otherwise exits 2
without a TPU, 1 on a mismatch. Its times are of the step alone, one thread,
nothing else on the host: not a cell's.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]
SCOPES = ("concurrent_release", "concurrent_expire", "concurrent_admit",
          "concurrent_issue")


def load(config_file: str):
    from sentinel_tpu.engine.concurrent import ConcurrentConfig

    with open(config_file, encoding="utf-8") as f:
        spec = json.load(f)
    buckets = sorted(spec["serve_buckets"])
    return spec, buckets, ConcurrentConfig(
        int(spec["engine"]["max_flows"]), int(spec["max_tokens"]),
        buckets[-1])


def compile_only(config_file: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from sentinel_tpu.engine import concurrent as CE

    _spec, buckets, cfg = load(config_file)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        jax.eval_shape(lambda: CE.make_concurrent_state(cfg)))
    # blocked_cumsum asks the backend which lowering to take: the chip's
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        for bucket in buckets:
            packed = jax.ShapeDtypeStruct((CE.PACKED_LINES, bucket),
                                          jnp.int32, sharding=one)
            t0 = time.perf_counter()
            compiled = CE.make_concurrent_step(cfg, bucket).lower(
                state, packed).compile()
            mem = compiled.memory_analysis()
            text = compiled.as_text()
            print(f"compiled jit_concurrent_step_b{bucket} for a described "
                  f"v5e in {time.perf_counter() - t0:.1f} s: argument bytes "
                  f"{mem.argument_size_in_bytes}, temp bytes "
                  f"{mem.temp_size_in_bytes}, alias bytes "
                  f"{mem.alias_size_in_bytes}; while loops "
                  f"{len(re.findall(r' while[(]', text))}, scatters "
                  f"{len(re.findall(r' scatter[(]', text))}", flush=True)
    finally:
        jax.default_backend = real
    print(f"state {CE.state_bytes(cfg)} bytes ({cfg.max_flows} rule slots, "
          f"a table of {cfg.table_len} token slots, {cfg.expire_block} "
          f"examined a step)")


def exactness(config_file: str, steps: int = 40) -> int:
    """The service at the configuration's size against the reference."""
    import numpy as np

    from cellbench.families import concurrent_reference as R
    from sentinel_tpu.cluster.concurrent import ConcurrentFlowRule
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.core import clock as clock_mod
    from sentinel_tpu.core.clock import ManualClock
    from sentinel_tpu.engine import EngineConfig

    spec, buckets, cfg = load(config_file)
    levels = {7: 3, 64: 5, 9_999: 40, 70_001: 1, 99_999: 64, 5: 0}
    clock = ManualClock()
    prev = clock_mod.set_clock(clock)
    bad = 0
    try:
        e = spec["engine"]
        svc = DefaultTokenService(
            EngineConfig(max_flows=cfg.max_flows,
                         max_namespaces=int(e["max_namespaces"]),
                         batch_size=buckets[-1]),
            serve_buckets=buckets, fuse_depths=(),
            concurrent_max_tokens=cfg.max_tokens)
        svc.load_concurrent_rules(
            [ConcurrentFlowRule(f, lv) for f, lv in levels.items()])
        svc.close()
        ref = R.Reference(levels, 2000)
        rng = np.random.default_rng(41)
        ref_id, gone = {}, []
        size = {f: 1 + k % 3 for k, f in enumerate(levels)}
        size[123] = 1
        flows_all = list(size)
        for step in range(steps):
            live = list(ref_id)
            n_acq = int(rng.integers(0, 200))
            flows = rng.choice(flows_all, n_acq)
            back = [live[i] for i in rng.permutation(len(live))[:int(
                rng.integers(0, len(live) + 1))]]
            rel_ids = back + ([int(rng.choice(gone))] if gone else []) + [
                0, -3, 10**15] + back[:1]
            order = rng.permutation(n_acq + len(rel_ids))
            ids = np.concatenate([flows, rel_ids]).astype(np.int64)[order]
            rel = np.concatenate([np.zeros(n_acq, bool),
                                  np.ones(len(rel_ids), bool)])[order]
            counts = np.array([0 if r else size[int(i)]
                               for i, r in zip(ids, rel)], np.int32)
            status, remaining, _w, tokens = svc.request_concurrent_batch(
                ids, counts, rel)
            now = clock.now_ms() - 1_700_000_000_000
            want = np.zeros(len(ids), np.int8)
            want_rem = np.zeros(len(ids), np.int32)
            for i in np.flatnonzero(rel):
                tok = ref_id.pop(int(ids[i]), 0)
                want[i] = ref.release(tok)
                if tok:
                    gone.append(int(ids[i]))
            ref.expire(now)
            for i in np.flatnonzero(~rel):
                st, rm, tok = ref.acquire(now, int(ids[i]), int(counts[i]))
                want[i], want_rem[i] = st, rm
                if st == 0:
                    bad += int(tokens[i] == 0 or int(tokens[i]) in ref_id)
                    ref_id[int(tokens[i])] = tok
            bad += int((status != want).sum())
            bad += int((remaining[~rel] != want_rem[~rel]).sum())
            held = {f: h for f, h in svc.concurrent_stats()["held"].items()
                    if h}
            bad += int(held != {f: h for f, h in ref.held.items() if h})
            if step % 9 == 8:
                clock.advance(2001)
                gone += list(ref_id)
                ref_id.clear()
                for _ in range(17):  # once round the ring
                    svc.concurrent_tick()
                ref.expire(clock.now_ms() - 1_700_000_000_000)
            else:
                clock.advance(int(rng.integers(0, 300)))
        print(f"exact: {steps} dispatches on {cfg.max_flows} rule slots and "
              f"a ring of {cfg.max_tokens} tokens, {bad} mismatches",
              flush=True)
    finally:
        clock_mod.set_clock(prev)
    return bad


def _scope_ms(profile_dir: str, program: str, hlo: str):
    """``(device ms a run of ``program``, {scope: ms a run}, [[op, us a
    run, scope]] of the eight longest)`` from the profiler's trace; an
    operation's scope is read off the compiled program's own text. ``(None,
    {}, [])`` where the trace holds no TPU plane."""
    from cellbench import trace as T

    scope = {m.group(1): m.group(2) for m in re.finditer(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", hlo, re.M)}
    try:
        planes = T.Trace(T.find_xplane(profile_dir)).devices
    except (FileNotFoundError, ValueError):
        planes = {}
    for plane in planes.values():
        names, _start, dur = plane["modules"]
        mine = [i for i, n in enumerate(names) if str(n).startswith(program)]
        if not mine:
            continue
        by_scope, ops = dict.fromkeys(SCOPES + ("other",), 0.0), {}
        for name, d in zip(*plane["ops"][::2]):
            name = T.short_name(name)
            where = next((s for s in SCOPES if s in scope.get(name, "")),
                         "other")
            by_scope[where] += d
            ops[(name, where)] = ops.get((name, where), 0.0) + d
        runs = len(mine)
        return (float(dur[mine].sum()) / runs / 1e6,
                {k: v / runs / 1e6 for k, v in by_scope.items()},
                [[op, ns / runs / 1e3, where] for (op, where), ns in
                 sorted(ops.items(), key=lambda kv: -kv[1])[:8]])
    return None, {}, []


def step_alone(config_file: str) -> None:
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sentinel_tpu.engine import concurrent as CE

    spec, buckets, cfg = load(config_file)
    rng = np.random.default_rng(1)
    state = CE.make_concurrent_state(cfg)
    # the cell's table: every flow ruled, the hot ranks capped
    n_ns = int(spec["rules"]["namespaces"])
    level = np.full(cfg.max_flows, int(spec["rules"]["unmetered_level"]),
                    np.int32)
    for rank, lv in enumerate(spec["rules"]["metered_levels"]):
        level[rank * n_ns:(rank + 1) * n_ns] = lv
    state = state._replace(
        level=jnp.asarray(level),
        timeout_ms=jnp.full(cfg.max_flows, 2000, jnp.int32))
    for bucket in buckets:
        step = CE.make_concurrent_step(cfg, bucket)

        def rows(now, tok_slot, tok_gen):
            # a frame's worth: Zipf-ish slots, grouped, one token a row
            slots = np.sort(np.minimum(
                rng.zipf(1.3, bucket) - 1, cfg.max_flows - 1)).astype(
                    np.int32)
            order = np.lexsort((tok_slot, tok_gen))
            return CE.pack_concurrent_rows(
                bucket, slots, np.ones(bucket, np.int32), tok_slot[order],
                tok_gen[order], now)

        none = np.zeros(0, np.int32)
        packed = rows(1_000, none, none)
        t0 = time.perf_counter()
        state, verdicts = step(state, packed)
        host = np.asarray(verdicts)
        first = time.perf_counter() - t0

        def chain(n, now, state, host):
            for k in range(n):
                ok = host[CE.OUT_STATUS] == CE.ST_OK
                packed = rows(now + k, host[CE.OUT_ID_SLOT][ok],
                              host[CE.OUT_ID_GEN][ok])
                state, verdicts = step(state, packed)
                host = np.asarray(verdicts)
            return state, host

        t0 = time.perf_counter()
        state, host = chain(20, 1_100, state, host)
        ms = (time.perf_counter() - t0) / 20 * 1e3
        n_ok = int((host[CE.OUT_STATUS] == CE.ST_OK).sum())
        n_rel = int((host[CE.OUT_RELEASE][:bucket] == CE.ST_RELEASE_OK).sum())
        print(f"step b{bucket}: first call {first:.2f} s, {ms:.3f} ms/step "
              f"(host packing and one read a step included); last step "
              f"{n_ok} OK of {bucket} acquires, {n_rel} RELEASE_OK",
              flush=True)
        hlo = step.lower(state, packed).compile().as_text()
        profile = tempfile.mkdtemp(prefix="concurrent_step_")
        try:
            jax.profiler.start_trace(profile)
            state, host = chain(20, 1_200, state, host)
            jax.profiler.stop_trace()
            device, by_scope, ops = _scope_ms(
                profile, f"jit_concurrent_step_b{bucket}", hlo)
        finally:
            shutil.rmtree(profile, ignore_errors=True)
        if device is None:
            print(f"scopes b{bucket}: not measured (no TPU plane in the "
                  f"profile)", flush=True)
        else:
            print(f"scopes b{bucket}: device {device:.4f} ms/step; "
                  + ", ".join(f"{k} {v:.4f}" for k, v in by_scope.items())
                  + " ms; longest: " + ", ".join(
                      f"{op} {us:.1f} us ({where})" for op, us, where in ops),
                  flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}, "
          f"bytes_in_use {stats.get('bytes_in_use')}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default=os.path.join(
        ROOT, "cellbench", "configs", "concurrent-mesh-100k.json"))
    ap.add_argument("--compile", action="store_true",
                    help="compile for a described v5e, no chip needed")
    ap.add_argument("--cpu", action="store_true",
                    help="prove the script on the CPU (give it a tiny "
                         "configuration)")
    args = ap.parse_args()
    if args.compile:
        return compile_only(args.config)
    import jax

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    if dev.platform != "tpu" and not args.cpu:
        print("needs a TPU: no result")
        raise SystemExit(2)
    bad = exactness(args.config)
    step_alone(args.config)
    print(f"concurrent_chip_check: {bad} mismatches")
    raise SystemExit(1 if bad else 0)


if __name__ == "__main__":
    main()
