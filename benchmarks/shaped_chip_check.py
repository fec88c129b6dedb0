"""Traffic shaping on the chip, before any cell: exactness at the deployment's
size, then the step alone with its shaping arms live against dead.

    python3 benchmarks/shaped_chip_check.py [--config cellbench/configs/shaped-mesh-100k.json]

1. The comparisons of ``tests/test_shaped_reference.py`` (``engine.decide``
   and the fused serve step against the plain reference on seeded tables of
   all four behaviours, every status and wait) on the TPU backend, at the
   tests' small geometry.
2. The shaped family's nine probe checks against a ``DefaultTokenService``
   holding the configuration's 100k rules, in process, every frame padded
   with rows on unmetered flows of the traffic namespaces to one full
   16384-row dispatch: the size a backlog gives the step in the cell.
3. The serve step of the mix's usual bucket (1024) chained on the donated
   state, on the cell's own rows: once with the configuration's rules and
   priority flags (every arm live), once with the same rows unprioritized
   on the same table loaded without shaping (every arm dead). Milliseconds a
   step, and what each step said of its arms.

Exits 2 without a TPU, 1 on a mismatch. Its times are of the step alone, one
thread, nothing else on the host: not a cell's. ``--cpu`` runs it on the CPU
backend at the tiny test configuration, to prove the script.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]


def small_tables() -> int:
    import test_shaped_reference as T

    bad = 0
    for seed in range(6):
        try:
            T.test_decide_equals_the_plain_reference_row_for_row(seed)
            T.test_the_fused_serve_step_equals_it_and_says_which_arms_ran(
                seed + 6)
            print(f"exact[small tables] seed {seed}: 0 mismatches", flush=True)
        except AssertionError as e:
            bad += 1
            print(f"exact[small tables] seed {seed}: MISMATCH "
                  f"{str(e)[:300]}", flush=True)
    try:
        T.test_booked_tokens_count_for_all_of_their_window()
        T.test_a_step_of_default_rows_says_no_arm_ran()
    except AssertionError as e:
        bad += 1
        print(f"exact[small tables] by hand: MISMATCH {str(e)[:300]}",
              flush=True)
    return bad


class InProcessProbe:
    """What ``shaped._Checks`` asks of ``probe.Probe``, with the service's
    public batch entry in the door's place and every frame filled up to
    ``frame_rows`` with rows that touch nothing the checks read."""

    def __init__(self, service, dep, tr, seed: int, frame_rows: int):
        import numpy as np

        self.service, self.dep, self.tr = service, dep, tr
        self.rng = np.random.default_rng([seed, 7919])
        self.single, self.frame_rows, self.probe_set = False, frame_rows, 0
        self.checks = []
        self.say = lambda msg: print(msg, flush=True)

    def send(self, ids, acq, prio):
        import numpy as np

        status, wait, took = [], [], 0.0
        lo = len(self.dep.metered_counts)
        for at in range(0, len(ids), self.frame_rows):
            part = slice(at, at + self.frame_rows)
            n = len(ids[part])
            pad = self.frame_rows - n
            ns = self.rng.choice(self.dep.traffic_namespaces(), size=pad)
            rank = self.rng.integers(lo, self.dep.flows_per_namespace(),
                                     size=pad)
            t0 = time.monotonic()
            s, _remaining, w = self.service.request_batch_arrays(
                np.concatenate([ids[part], self.dep.flow_id(ns, rank)]),
                np.concatenate([acq[part], np.ones(pad, np.int32)]),
                np.concatenate([prio[part], np.zeros(pad, np.uint8)]))
            took += time.monotonic() - t0
            if pad and not (s[n:] == 0).all():
                raise RuntimeError("a padding row did not pass")
            status.append(s[:n])
            wait.append(w[:n])
        return (np.concatenate(status).astype(np.int8),
                np.concatenate(wait).astype(np.int32), took)

    def record(self, name, rows, mismatches, took, note="") -> None:
        self.checks.append((name, int(mismatches)))
        self.say(f"exact[{self.frame_rows}-row frames] {name}: {rows} rows, "
                 f"{mismatches} mismatches, {took * 1e3:.1f} ms{note}")


def build(dep, unshaped: bool = False):
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import EngineConfig

    e = dep.spec["engine"]
    service = DefaultTokenService(
        EngineConfig(max_flows=int(e["max_flows"]),
                     max_namespaces=int(e["max_namespaces"]),
                     batch_size=int(e["batch_size"]),
                     bucket_ms=int(e["bucket_ms"]),
                     n_buckets=int(e["n_buckets"])),
        serve_buckets=tuple(dep.spec["serve_buckets"]),
        fuse_depths=tuple(dep.spec["fuse_depths"]))
    dep.family.load_rules(service, dep)
    if unshaped:
        dep.family.unshaped(service)
    return service


def full_frames(dep, tr, seed: int) -> int:
    service = build(dep)
    try:
        service.warmup()
        p = InProcessProbe(service, dep, tr, seed,
                           int(dep.spec["engine"]["batch_size"]))
        for check in dep.family.probe_checks(p):
            check()
    finally:
        service.close()
    return sum(bad for _name, bad in p.checks) + (len(p.checks) != 9)


def step_alone(dep, tr, seed: int, bucket: int, steps: int = 200) -> None:
    import jax
    import numpy as np

    from sentinel_tpu.engine.decide import (HEAD_NOW, ROW_HEAD, pack_requests,
                                            unpack_arms)

    ids, acq, prio = (c.reshape(-1, bucket) for c in dep.family.Mix(
        tr, dep, seed, 1).frames(steps * bucket // int(tr["frame_rows"])))
    for label, unshaped in (("arms live", False), ("arms dead", True)):
        service = build(dep, unshaped)
        try:
            cfg = service.config._replace(batch_size=bucket)
            step = service._step_fn(bucket, False)
            state, table = service._state, service._table
            service._state = None  # the step donates it
            packed = []
            for k in range(len(ids)):
                slots = service._lookup_from(service._lookup, ids[k])
                order = np.argsort(slots, kind="stable")
                packed.append(pack_requests(
                    cfg, slots[order], acq[k][order],
                    None if unshaped else prio[k][order]))
            for rounds in range(2):  # the first compiles
                t0 = time.perf_counter()
                for k, rows in enumerate(packed):
                    rows[ROW_HEAD, HEAD_NOW] = 1_000 + 3 * k
                    state, verdicts = step(state, table, rows)
                    if rounds:
                        verdicts.copy_to_host_async()
                jax.block_until_ready(verdicts)
                took = time.perf_counter() - t0
            arms = unpack_arms(np.asarray(verdicts))
            print(f"step b{bucket} {label}: {took / len(packed) * 1e3:.3f} "
                  f"ms/step over {len(packed)} chained steps of the mix's "
                  f"rows; the last said live bits {arms[0]}, shaped "
                  f"{arms[1]}, paced {arms[2]}, prioritized {arms[3]} rows",
                  flush=True)
        finally:
            service.close()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default=os.path.join(
        ROOT, "cellbench", "configs", "shaped-mesh-100k.json"))
    ap.add_argument("--traffic", default=os.path.join(
        ROOT, "cellbench", "traffic", "tenants-zipf-prio-open.json"))
    ap.add_argument("--seed", type=int, default=2_147_483_777)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        extra = os.path.join(ROOT, "cellbench", "tests", "extra")
        args.config = os.path.join(extra, "configs", "tiny-shaped.json")
        args.traffic = os.path.join(extra, "traffic", "tiny-prio-open.json")
    import jax

    from cellbench import deploy

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    if dev.platform != "tpu" and not args.cpu:
        print("needs a TPU: no result")
        raise SystemExit(2)
    dep = deploy.load(args.config)
    tr = deploy.load_json(args.traffic)
    bad = small_tables()
    bad += full_frames(dep, tr, args.seed)
    step_alone(dep, tr, args.seed, sorted(dep.spec["serve_buckets"])[1])
    print(f"shaped_chip_check: {bad} mismatches")
    raise SystemExit(1 if bad else 0)


if __name__ == "__main__":
    main()
