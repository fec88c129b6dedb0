"""Cross-service authority over HTTP: the caller's identity travels in the
``X-Sentinel-Origin`` header and authority rules enforce it on the callee.

reference: the dubbo adapter's origin propagation
(``SentinelDubboConsumerFilter``/``SentinelDubboProviderFilter`` attachment
pair) and the servlet ``CommonFilter``'s origin header — here as a real WSGI
service guarded by ``SentinelWsgiMiddleware`` plus an outbound header
injected by ``adapters.origin``.

billing-svc is whitelisted for ``GET:/admin``; report-svc is not.
"""

import os
import sys
import threading
import urllib.error
import urllib.request
from wsgiref.simple_server import WSGIServer, make_server

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from sentinel_tpu.adapters.origin import ORIGIN_HEADER
from sentinel_tpu.adapters.wsgi import SentinelWsgiMiddleware
from sentinel_tpu.local.authority import (
    AuthorityRule,
    AuthorityRuleManager,
    AuthorityStrategy,
)


def app(environ, start_response):
    start_response("200 OK", [("Content-Type", "text/plain")])
    return [b"admin ok"]


def call(port: int, origin: str) -> int:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/admin", headers={ORIGIN_HEADER: origin}
    )
    try:
        with urllib.request.urlopen(req, timeout=5) as rsp:
            return rsp.status
    except urllib.error.HTTPError as e:
        return e.code


def main() -> None:
    AuthorityRuleManager.load_rules([
        AuthorityRule(
            resource="GET:/admin",
            limit_app="billing-svc",
            strategy=AuthorityStrategy.WHITE,
        )
    ])
    guarded = SentinelWsgiMiddleware(app)

    class QuietServer(WSGIServer):
        def handle_error(self, request, client_address):  # demo: no tracebacks
            pass

    server = make_server("127.0.0.1", 0, guarded, server_class=QuietServer)
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        allowed = call(port, "billing-svc")
        denied = call(port, "report-svc")
        print(f"billing-svc -> {allowed} (whitelisted)")
        print(f"report-svc  -> {denied} (blocked by authority rule)")
        assert allowed == 200 and denied == 429, (allowed, denied)
    finally:
        server.shutdown()
        AuthorityRuleManager.load_rules([])


if __name__ == "__main__":
    main()
