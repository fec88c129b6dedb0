"""Two sets of runs of one cell, as the bound rule asks: each run a new
process with another seed, the same seeds in both sets. Prints every run's
end-to-end metrics, and per metric and set the median and the spread (the
distance between the quartiles of ``statistics.quantiles(values, n=4)`` as a
share of the median). This parent never loads JAX.

    python3 cellbench/tools/measure_sets.py --workload <cell> --seconds 20 \
        --runs 6 --sets 2 --first-seed 2147480000 [--out chiprun_out/x.json]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one_run(workload: str, seed: int, seconds: float, trace: int,
            log=None) -> dict:
    out = subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=ROOT, capture_output=True, text=True)
    lines = out.stdout.strip().splitlines()
    if log:
        with open(log, "a", encoding="utf-8") as f:
            f.write(out.stdout + out.stderr[-2000:])
    if out.returncode != 0 or not lines:
        sys.stderr.write(out.stdout[-4000:] + out.stderr[-4000:])
        raise SystemExit(f"run of {workload} seed {seed} exited "
                         f"{out.returncode}")
    for ln in lines:
        if ("COMPILED INSIDE" in ln or "mismatches" in ln
                and " 0 mismatches" not in ln or "window " in ln
                and "failed 0 " not in ln):
            print(ln, flush=True)
    return json.loads(lines[-1])


def spread(values: list) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2_147_480_000)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    sets = []
    for s in range(args.sets):
        runs = []
        for r in range(args.runs):
            res = one_run(args.workload, args.first_seed + r, args.seconds,
                          args.trace, log=os.path.join(
                              ROOT, args.out + ".log") if args.out else None)
            row = {k: v["value"] for k, v in res["metrics"].items()}
            row.update(seed=args.first_seed + r, correct=res["correct"],
                       attempted=res["attempted"], failed=res["failed"],
                       peak=res["device"]["memory_peak_bytes"])
            runs.append(row)
            print(f"set {s} run {r}: " + json.dumps(row), flush=True)
        sets.append(runs)
    names = [k for k in sets[0][0] if k not in (
        "seed", "correct", "attempted", "failed", "peak")]
    table = {}
    for name in names:
        table[name] = []
        for s, runs in enumerate(sets):
            vals = [r[name] for r in runs if name in r]
            first_left_out = vals[1:] if name == "setup_s" and s == 0 else vals
            table[name].append({
                "median": statistics.median(vals),
                "spread": spread(vals) if len(vals) >= 2 else None,
                "median_without_first": statistics.median(first_left_out),
                "min": min(vals), "max": max(vals)})
    summary = {"workload": args.workload, "seconds": args.seconds,
               "all_correct": all(r["correct"] for rs in sets for r in rs),
               "failed_rows": sum(r["failed"] for rs in sets for r in rs),
               "table": table}
    print(json.dumps(summary, indent=1), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
        with open(os.path.join(ROOT, args.out), "w", encoding="utf-8") as f:
            json.dump({"summary": summary, "sets": sets}, f, indent=1)


if __name__ == "__main__":
    main()
