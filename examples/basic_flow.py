"""The demo-basic slice: one QPS=20 flow rule on "HelloWorld".

reference: ``sentinel-demo-basic/.../flow/FlowQpsDemo.java`` — expect ~20
passes per second, the rest blocked.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import time

from sentinel_tpu.local import BlockException
from sentinel_tpu.local.flow import FlowRule, FlowRuleManager
from sentinel_tpu.local.sph import entry


def main(seconds: float = 2.0) -> None:
    FlowRuleManager.load_rules([FlowRule(resource="HelloWorld", count=20)])
    deadline = time.time() + seconds
    second = int(time.time())
    passed = blocked = 0
    while time.time() < deadline:
        try:
            with entry("HelloWorld"):
                passed += 1
        except BlockException:
            blocked += 1
        if int(time.time()) != second:
            print(f"second {second}: pass={passed} block={blocked}")
            second, passed, blocked = int(time.time()), 0, 0
        time.sleep(0.001)
    print(f"second {second}: pass={passed} block={blocked}")


if __name__ == "__main__":
    main()
