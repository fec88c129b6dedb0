"""A guarded ASGI app with the command center mounted in the same server —
the control plane rides the app's own event loop.

reference: the servlet ``CommonFilter`` + ``sentinel-transport-netty-http``
(command handlers on the app's netty loop). Here: SentinelAsgiMiddleware
guards the app, ``command_asgi_app()`` serves the command surface from the
same process with no extra thread server, and a rule pushed through that
surface takes effect immediately.
"""

import asyncio
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import json

from sentinel_tpu.adapters.asgi import SentinelAsgiMiddleware
from sentinel_tpu.core import clock as clock_mod
from sentinel_tpu.core.clock import ManualClock
from sentinel_tpu.local.flow import FlowRuleManager
from sentinel_tpu.transport.command_asgi import command_asgi_app


async def hello_app(scope, receive, send):
    await send({"type": "http.response.start", "status": 200, "headers": []})
    await send({"type": "http.response.body", "body": b"ok"})


async def call(app, path, method="GET", body=b"", query=""):
    sent = []
    scope = {"type": "http", "method": method, "path": path,
             "query_string": query.encode(), "client": ("127.0.0.1", 1)}
    chunks = [{"type": "http.request", "body": body}]

    async def receive():
        return chunks.pop(0)

    async def send(msg):
        sent.append(msg)

    await app(scope, receive, send)
    status = next(m["status"] for m in sent
                  if m["type"] == "http.response.start")
    data = b"".join(m.get("body", b"") for m in sent
                    if m["type"] == "http.response.body")
    return status, data


async def main() -> None:
    # manual clock: the exact 2-pass/3-block assertion must not depend on
    # wall-clock window rolls (FAST_EXAMPLES determinism contract)
    prev = clock_mod.set_clock(ManualClock())
    app = SentinelAsgiMiddleware(hello_app)      # the guarded business app
    control = command_asgi_app()                 # the embedded control plane

    # push a QPS=2 rule through the control surface (what the dashboard does)
    rules = json.dumps([{"resource": "GET:/pay", "count": 2}]).encode()
    status, body = await call(control, "/setRules", "POST", rules,
                              query="type=flow")
    assert status == 200 and b"success" in body

    outcomes = [await call(app, "/pay") for _ in range(5)]
    codes = [s for s, _ in outcomes]
    print("statuses after pushing QPS=2 through the ASGI control plane:",
          codes)
    assert codes.count(200) == 2 and codes.count(429) == 3

    status, body = await call(control, "/getRules", query="type=flow")
    print("control plane sees:", json.loads(body))
    FlowRuleManager.load_rules([])
    clock_mod.set_clock(prev)


if __name__ == "__main__":
    asyncio.run(main())
