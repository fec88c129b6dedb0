"""The one-time sweep that fixes a cell's rate or in-flight count.

    python3 -m cellbench.sweep --workload <cell> --key rate_rows_per_s \
        --values 200000,240000,280000 --runs 3 --seconds 8

Builds the cell's server once, then for every value of ``--key`` (a key of
the cell's traffic file) makes ``--runs`` windows with fresh generators and
seeds, and prints one line per window and a table. The knee of an open-loop
cell is the highest rate at which every run has no failed row and p95 under
the 20 ms budget; the cell then offers four fifths of it. A closed-loop
cell takes the fewest frames in flight at which the decided rate stops
rising with no row shed. The value chosen is written into the traffic file
by hand, with the table in PERF.md.
"""

from __future__ import annotations

import argparse
import copy
import json
import time

from cellbench import deploy, manifest, run


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--key", required=True)
    ap.add_argument("--values", required=True)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=2_500_000_000)
    ap.add_argument("--manifest", default=manifest.ROOT + "/BENCHMARK.json")
    args = ap.parse_args()
    cell = manifest.Cell(args.manifest, args.workload)
    dep = deploy.load(cell.config_file, cell.dirs)
    devices, say, compiles = run.start_jax(cell, require_chip=True)
    from cellbench import server as sut

    built = sut.build(dep, devices, say)
    table = []
    try:
        first = True
        for v in args.values.split(","):
            value = float(v) if "." in v or "e" in v else int(v)
            for r in range(args.runs):
                point = copy.copy(cell)
                point.traffic = dict(cell.traffic, **{args.key: value})
                work = run.work_dir(f"sweep-{args.workload}")
                seed = args.seed + 1000 * len(table) + r
                clients = run.Clients(point, seed, args.seconds, work, say)
                try:
                    run.connect(clients, built)
                    if first:
                        run.warm_up(built, clients, point, dep, seed,
                                    compiles, say)
                        first = False
                    else:
                        clients.command("warm")
                        time.sleep(dep.window_ms / 1000.0 + 0.2)
                    _t0, c0, c1, sliced, in_w, results = run.window(
                        clients, point, args.seconds, 0, work, compiles, say,
                        dep.family.progress(built))
                    client = run.merge_clients(results, work, clients)
                finally:
                    clients.close()
                e2e = run.e2e_metrics(client, point.traffic["loop"] == "closed",
                                      args.seconds)
                shed = sum(c1["shed"].values()) - sum(c0["shed"].values())
                row = {args.key: value, "run": r,
                       "attempted": client["attempted"],
                       "failed": client["failed_rows"], "server_shed": shed,
                       "compiles": len(in_w),
                       "slowest_reply_ms": float(client["lat_max"].max() * 1e3),
                       "gap_max_ms": sliced["stalls"]["gap_max_s"] * 1e3,
                       "stall_max_ms": sliced["stalls"]["stall_max_s"] * 1e3,
                       "gc_max_ms": sliced["stalls"]["gc_max_s"] * 1e3,
                       "rate": e2e["decided_verdicts_per_s"],
                       "p50_ms": e2e.get("verdict_latency_p50_ms"),
                       "p95_ms": e2e.get("verdict_latency_p95_ms")}
                table.append(row)
                say("sweep " + json.dumps(row))
    finally:
        built.close()
    print(json.dumps({"sweep": table}), flush=True)


if __name__ == "__main__":
    main()
