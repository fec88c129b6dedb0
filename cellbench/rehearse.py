"""The CPU rehearsal: every kind of cell at a tiny size, on the CPU backend
with four virtual devices, before any chip call. Proves paths, arguments and
control flow; its numbers mean nothing and are printed under ``cpu``.

    python3 -m cellbench.rehearse [workload ...]
"""

from __future__ import annotations

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

from cellbench import run  # noqa: E402

MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests",
                        "manifest.json")


def main() -> None:
    with open(MANIFEST, encoding="utf-8") as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    bad = 0
    for name in sys.argv[1:] or cells:
        result = run.run_cell(MANIFEST, name, seed=3_000_000_019, seconds=2.0,
                              trace=0, require_chip=False)
        print(json.dumps(result), flush=True)
        bad += not result["correct"]
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
