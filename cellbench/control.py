"""The readings behind the probe's limit, taken on the chip at the cell's
own size: the program sound, and under a control, probed on many seeds in
one process.

    python3 -m cellbench.control --workload <cell> --seeds 12 \
        [--control over_admit|lower_precision]

Each seed drives the cell's own load for a short window (so the probe meets
the server as a run's probe does), lets the rule windows drain, and probes.
Sound runs must read 0 mismatches on every seed; a control has to read more.
The benchmark's own runs never call this.

A control that breaks a guarantee is the deployment's family's (its
``CONTROLS``). The flow family's ``over_admit`` breaks the guarantee the
deployment's file states first (no admission beyond a rule's count): the
service's answers pass through ``OverAdmit``, which turns the first BLOCKED
verdict of every dispatch into OK, one row where the answer is produced. This
is the control the limit is held against.

``lower_precision`` wraps ``jax.numpy.einsum`` and ``jax.numpy.matmul``
before the service compiles anything, so that every ``precision=`` the
program asks for becomes the default (one bfloat16 pass on the TPU). On this
program it reads 0 mismatches too (PERF.md, PR 23): the step's matmul
operands are 0/1 masks and per-block totals of at most 128 rows, which an
8-bit mantissa carries exactly. It is kept to show that, not as the control.
"""

from __future__ import annotations

import argparse
import json
import time

from cellbench import deploy, manifest, probe, run


def lower_the_precision() -> None:
    import jax.numpy as jnp

    def without_precision(fn):
        def wrapped(*args, **kw):
            kw.pop("precision", None)
            return fn(*args, **kw)
        return wrapped

    jnp.einsum = without_precision(jnp.einsum)
    jnp.matmul = without_precision(jnp.matmul)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_147_480_000)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", default="none",
                    help="none, lower_precision, or one of the family's")
    ap.add_argument("--manifest", default=manifest.ROOT + "/BENCHMARK.json")
    args = ap.parse_args()
    cell = manifest.Cell(args.manifest, args.workload)
    dep = deploy.load(cell.config_file, cell.dirs)
    controls = dep.family.CONTROLS
    if args.control not in ("none", "lower_precision", *controls):
        raise SystemExit(f"no control {args.control!r}; the family has "
                         f"{sorted(controls)}")
    devices, say, compiles = run.start_jax(cell, require_chip=True)
    if args.control == "lower_precision":
        lower_the_precision()
    say(f"control: {args.control}")
    from cellbench import server as sut

    built = sut.build(dep, devices, say,
                      wrap_service=controls.get(args.control))
    readings = []
    try:
        for k in range(args.seeds):
            seed = args.first_seed + k
            work = run.work_dir(f"control-{args.workload}")
            clients = run.Clients(cell, seed, args.seconds, work, say)
            try:
                run.connect(clients, built)
                if k == 0:
                    run.warm_up(built, clients, cell, dep, seed, compiles, say)
                _t0, _c0, _c1, _s, _w, results = run.window(
                    clients, cell, args.seconds, 0, work, compiles, say,
                    dep.family.progress(built))
                client = run.merge_clients(results, work, clients)
            finally:
                clients.close()
            time.sleep(dep.window_ms / 1000.0 + 0.2)
            out = probe.Probe(built.server.port, dep, cell.traffic, seed,
                              say=say).run()
            sound = run.window_invariants(client, dep, say)
            time.sleep(dep.window_ms / 1000.0 + 0.2)
            row = {"seed": seed, "probe_ok": out["ok"], "window_ok": sound,
                   "failed": client["failed_rows"],
                   "mismatches": {c["check"]: c.get("mismatches", "error")
                                  for c in out["checks"]}}
            readings.append(row)
            say("control reading " + json.dumps(row))
    finally:
        built.close()
    print(json.dumps({"control": args.control, "readings": readings}),
          flush=True)


if __name__ == "__main__":
    main()
