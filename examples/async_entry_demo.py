"""Detached entries under asyncio: guard N concurrent downstream calls from
one coroutine, completing out of order.

reference: ``AsyncEntryDemo.java`` (SphU.asyncEntry).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import asyncio
import random

from sentinel_tpu.local import BlockException
from sentinel_tpu.local.chain import get_cluster_node
from sentinel_tpu.local.flow import FlowRule, FlowRuleManager
from sentinel_tpu.local.sph import async_entry


async def downstream_call(i: int) -> str:
    try:
        e = async_entry("asyncRpc")
    except BlockException:
        return f"call {i}: blocked"
    try:
        await asyncio.sleep(random.uniform(0.01, 0.05))
        return f"call {i}: ok"
    except Exception as err:  # pragma: no cover - demo
        e.trace(err)
        raise
    finally:
        e.exit()


async def run() -> None:
    FlowRuleManager.load_rules([FlowRule(resource="asyncRpc", count=5)])
    results = await asyncio.gather(*(downstream_call(i) for i in range(8)))
    for line in results:
        print(line)
    node = get_cluster_node("asyncRpc")
    print(f"live concurrency after completion: {node.cur_thread_num}")
    print(f"avg rt over real call durations: {node.avg_rt():.1f}ms")
    FlowRuleManager.reset_for_tests()


if __name__ == "__main__":
    asyncio.run(run())
