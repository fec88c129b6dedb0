"""Dynamic rules from a watched file: edit the JSON, limits change live.

reference: ``sentinel-demo-dynamic-file-rule`` /
``FileRefreshableDataSource.java:39``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import json
import tempfile
import time

from sentinel_tpu.datasource.converters import flow_rules_from_json
from sentinel_tpu.datasource.file import FileRefreshableDataSource
from sentinel_tpu.local import BlockException
from sentinel_tpu.local.flow import FlowRuleManager
from sentinel_tpu.local.sph import entry


def admitted(n: int = 50) -> int:
    ok = 0
    for _ in range(n):
        try:
            with entry("res"):
                ok += 1
        except BlockException:
            pass
    return ok


def main() -> None:
    path = os.path.join(tempfile.mkdtemp(), "flow_rules.json")
    with open(path, "w") as f:
        json.dump([{"resource": "res", "count": 5}], f)

    ds = FileRefreshableDataSource(
        path, converter=flow_rules_from_json, refresh_interval_s=0.2
    )
    FlowRuleManager.register_property(ds.property)
    ds.start()
    try:
        print(f"rule file {path} says count=5  → admitted {admitted()}/50")
        with open(path, "w") as f:
            json.dump([{"resource": "res", "count": 30}], f)
        time.sleep(1.2)  # datasource polls and pushes the new rule
        print(f"edited file to count=30        → admitted {admitted()}/50")
    finally:
        ds.close()
        FlowRuleManager.reset_for_tests()


if __name__ == "__main__":
    main()
