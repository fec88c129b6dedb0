"""Control plane tests: datasources, command center, metric log, heartbeat."""

import json
import threading
import urllib.request

import pytest

import sentinel_tpu.local as sentinel
from sentinel_tpu.datasource import (
    FileRefreshableDataSource,
    FileWritableDataSource,
    WritableDataSourceRegistry,
    flow_rules_from_json,
    flow_rules_to_json,
)
from sentinel_tpu.local import BlockException, FlowRule, FlowRuleManager
from sentinel_tpu.metrics.log import MetricNode, MetricSearcher, MetricTimer, MetricWriter
from sentinel_tpu.transport.command import CommandCenter


@pytest.fixture(autouse=True)
def clean_engine(manual_clock):
    sentinel.reset_for_tests()
    WritableDataSourceRegistry.reset_for_tests()
    yield manual_clock
    WritableDataSourceRegistry.reset_for_tests()
    sentinel.reset_for_tests()


RULES_JSON = json.dumps(
    [{"resource": "ds_res", "count": 2, "grade": 1, "limitApp": "default"}]
)


class TestDatasources:
    def test_file_datasource_loads_and_follows_changes(self, tmp_path, manual_clock):
        path = tmp_path / "flow.json"
        path.write_text(RULES_JSON)
        ds = FileRefreshableDataSource(str(path), flow_rules_from_json,
                                       refresh_interval_s=0.05)
        FlowRuleManager.register_property(ds.property)
        ds.start()
        try:
            assert len(FlowRuleManager.get_rules("ds_res")) == 1
            assert FlowRuleManager.get_rules("ds_res")[0].count == 2
            # change the file → rules follow
            path.write_text(json.dumps([{"resource": "ds_res", "count": 9}]))
            deadline = threading.Event()
            for _ in range(100):
                if FlowRuleManager.get_rules("ds_res") and \
                        FlowRuleManager.get_rules("ds_res")[0].count == 9:
                    break
                deadline.wait(0.05)
            assert FlowRuleManager.get_rules("ds_res")[0].count == 9
        finally:
            ds.close()

    def test_sentinel_json_schema_roundtrip(self):
        rules = flow_rules_from_json(RULES_JSON)
        text = flow_rules_to_json(rules)
        again = flow_rules_from_json(text)
        assert again == rules

    def test_malformed_file_keeps_last_good_rules(self, tmp_path, manual_clock):
        path = tmp_path / "flow.json"
        path.write_text(RULES_JSON)
        ds = FileRefreshableDataSource(str(path), flow_rules_from_json)
        FlowRuleManager.register_property(ds.property)
        ds.refresh()
        assert len(FlowRuleManager.get_rules("ds_res")) == 1
        path.write_text("{not json")
        ds.refresh()  # swallowed, logged
        assert len(FlowRuleManager.get_rules("ds_res")) == 1


@pytest.fixture
def command_center():
    cc = CommandCenter(host="127.0.0.1", port=0).start()
    yield cc
    cc.stop()


def http_get(cc, path, timeout=5):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{cc.port}/{path}", timeout=timeout
    ) as r:
        return r.status, r.read().decode()


def http_post(cc, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{cc.port}/{path}", data=body.encode(), method="POST"
    )
    with urllib.request.urlopen(req, timeout=5) as r:
        return r.status, r.read().decode()


class TestCommandCenter:
    def test_api_lists_commands(self, command_center):
        status, body = http_get(command_center, "api")
        urls = {item["url"] for item in json.loads(body)}
        for expected in ("version", "getRules", "setRules", "metric",
                         "clusterNode", "basicInfo", "systemStatus"):
            assert f"/{expected}" in urls

    def test_version_and_basic_info(self, command_center):
        status, body = http_get(command_center, "version")
        assert "sentinel-tpu/" in body
        status, body = http_get(command_center, "basicInfo")
        info = json.loads(body)
        assert info["pid"] > 0

    def test_rule_crud_roundtrip(self, command_center):
        status, body = http_post(
            command_center, "setRules?type=flow",
            json.dumps([{"resource": "cmd_res", "count": 1}]),
        )
        assert body == "success"
        # rule actually enforced
        ok = blocked = 0
        for _ in range(3):
            try:
                with sentinel.entry("cmd_res"):
                    ok += 1
            except BlockException:
                blocked += 1
        assert (ok, blocked) == (1, 2)
        status, body = http_get(command_center, "getRules?type=flow")
        rules = json.loads(body)
        assert rules[0]["resource"] == "cmd_res"

    def test_gateway_api_definitions_roundtrip(self, command_center):
        from sentinel_tpu.adapters.gateway_api import (
            GatewayApiDefinitionManager,
        )

        try:
            defs = [{"apiName": "prod-api", "predicateItems": [
                {"pattern": "/product/", "matchStrategy": 1}]}]
            status, body = http_post(
                command_center, "gateway/updateApiDefinitions",
                json.dumps(defs),
            )
            assert body == "success"
            status, body = http_get(
                command_center, "gateway/getApiDefinitions"
            )
            got = json.loads(body)
            assert got == defs
            # the matcher actually picks the group up
            from sentinel_tpu.adapters.gateway_api import (
                GatewayApiMatcherManager,
            )

            assert GatewayApiMatcherManager.pick_matching_api_names(
                "/product/7"
            ) == ["prod-api"]
        finally:
            GatewayApiDefinitionManager.reset_for_tests()

    def test_set_rules_writes_through_datasource(self, command_center, tmp_path):
        from sentinel_tpu.datasource import converters as conv

        path = tmp_path / "flow_out.json"
        # the natural pairing: the handler hands *parsed rules* to the
        # registered serializer (ModifyRulesCommandHandler.java:58)
        WritableDataSourceRegistry.register(
            "flow", FileWritableDataSource(str(path), conv.flow_rules_to_json)
        )
        http_post(
            command_center, "setRules?type=flow",
            json.dumps([{"resource": "w_res", "count": 5}]),
        )
        saved = json.loads(path.read_text())
        assert saved[0]["resource"] == "w_res"
        assert saved[0]["count"] == 5

    def test_cluster_node_stats(self, command_center):
        with sentinel.entry("stat_cmd_res"):
            pass
        status, body = http_get(command_center, "clusterNode")
        nodes = json.loads(body)
        names = [n["resourceName"] for n in nodes]
        assert "stat_cmd_res" in names

    def test_unknown_command_404(self, command_center):
        try:
            http_get(command_center, "nonsense")
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
            assert "api" in e.read().decode()

    def test_cluster_mode_commands(self, command_center):
        status, body = http_get(command_center, "getClusterMode")
        assert json.loads(body)["mode"] == -1
        http_get(command_center, "setClusterMode?mode=0")
        status, body = http_get(command_center, "getClusterMode")
        assert json.loads(body)["mode"] == 0
        from sentinel_tpu.cluster import api as cluster_api

        cluster_api.reset_for_tests()

    def test_demotion_clears_embedded_service(self, command_center):
        # promote to SERVER (ephemeral port), then demote: the stopped
        # server's service must not keep answering cluster/server/* commands
        from sentinel_tpu.cluster import api as cluster_api

        try:
            # promotion warms up every serve-bucket kernel variant — allow
            # for the compiles
            status, body = http_get(
                command_center, "setClusterMode?mode=1&tokenPort=0", timeout=120
            )
            assert "success" in body
            assert cluster_api.get_embedded_server() is not None
            status, body = http_get(command_center, "cluster/server/info")
            assert status == 200 and "error" not in body
            http_get(command_center, "setClusterMode?mode=-1")
            assert cluster_api.get_embedded_server() is None
            status, body = http_get(command_center, "cluster/server/info")
            assert "error" in body  # 'not a token server'
        finally:
            cluster_api.reset_for_tests()


class TestAsgiCommandCenter:
    """ASGI-embedded command transport (netty-http/spring-mvc variant
    analog): same handler registry, served by the app's own server."""

    @staticmethod
    def _call(app, path, method="GET", query="", body=b""):
        import asyncio

        sent = []

        async def run():
            scope = {"type": "http", "method": method, "path": path,
                     "query_string": query.encode()}
            chunks = [{"type": "http.request", "body": body}]

            async def receive():
                return chunks.pop(0)

            async def send(msg):
                sent.append(msg)

            await app(scope, receive, send)

        asyncio.run(run())
        status = next(
            m["status"] for m in sent if m["type"] == "http.response.start"
        )
        out = b"".join(
            m.get("body", b"") for m in sent
            if m["type"] == "http.response.body"
        )
        return status, out

    def test_api_version_and_unknown(self):
        from sentinel_tpu.transport.command_asgi import command_asgi_app

        app = command_asgi_app()
        status, body = self._call(app, "/api")
        assert status == 200 and b"getRules" in body
        status, body = self._call(app, "/version")
        assert status == 200 and b"sentinel-tpu" in body
        status, _ = self._call(app, "/definitely-not-a-command")
        assert status == 404

    def test_rule_crud_matches_thread_server(self):
        from sentinel_tpu.transport.command_asgi import command_asgi_app

        app = command_asgi_app()
        rules = [{"resource": "asgi_res", "count": 7, "grade": 1}]
        status, body = self._call(
            app, "/setRules", method="POST", query="type=flow",
            body=json.dumps(rules).encode(),
        )
        assert status == 200 and b"success" in body
        status, body = self._call(app, "/getRules", query="type=flow")
        assert status == 200
        got = json.loads(body)
        assert any(r["resource"] == "asgi_res" for r in got)

    def test_body_size_cap(self):
        from sentinel_tpu.transport.command_asgi import command_asgi_app

        app = command_asgi_app(max_body_bytes=64)
        status, _ = self._call(
            app, "/setRules", method="POST", query="type=flow",
            body=b"x" * 128,
        )
        assert status == 413

    def test_lifespan_protocol(self):
        import asyncio

        from sentinel_tpu.transport.command_asgi import command_asgi_app

        app = command_asgi_app()
        sent = []

        async def run():
            msgs = [{"type": "lifespan.startup"}, {"type": "lifespan.shutdown"}]

            async def receive():
                return msgs.pop(0)

            async def send(msg):
                sent.append(msg["type"])

            await app({"type": "lifespan"}, receive, send)

        asyncio.run(run())
        assert sent == ["lifespan.startup.complete",
                        "lifespan.shutdown.complete"]


class TestMetricLog:
    def test_writer_searcher_roundtrip(self, tmp_path):
        w = MetricWriter(base_dir=str(tmp_path), single_file_size=10_000)
        nodes = [
            MetricNode(timestamp_ms=1_700_000_000_000, resource="res|pipe",
                       pass_qps=10, block_qps=2, rt=1.5),
            MetricNode(timestamp_ms=1_700_000_000_000, resource="other",
                       pass_qps=3),
        ]
        w.write(nodes)
        w.close()
        s = MetricSearcher(str(tmp_path), w.app)
        found = s.find(1_699_999_999_000, 1_700_000_001_000)
        assert len(found) == 2
        assert found[0].resource == "res_pipe"  # pipe escaped
        assert found[0].pass_qps == 10
        only = s.find(0, 2**61, identity="other")
        assert len(only) == 1 and only[0].pass_qps == 3

    def test_searcher_seeks_via_index(self, tmp_path):
        # many seconds of data; a narrow window must come back complete even
        # though the seek skips everything before it
        w = MetricWriter(base_dir=str(tmp_path), single_file_size=10_000_000)
        t0 = 1_700_000_000_000
        for i in range(200):
            w.write([MetricNode(timestamp_ms=t0 + i * 1000, resource="r",
                                pass_qps=i)])
        w.close()
        s = MetricSearcher(str(tmp_path), w.app)
        found = s.find(t0 + 150_000, t0 + 152_000)
        assert [n.pass_qps for n in found] == [150, 151, 152]
        # the seek really skipped: offset for a late window is deep in the file
        idx = str(tmp_path / f"{w.app}-metrics.log.0.idx")
        assert s._seek_offset(idx, t0 + 150_000) > 0

    def test_rolling_keeps_bounded_files(self, tmp_path):
        w = MetricWriter(base_dir=str(tmp_path), single_file_size=200,
                         total_file_count=3)
        for i in range(40):
            w.write([MetricNode(timestamp_ms=1_700_000_000_000 + i * 1000,
                                resource=f"r{i}", pass_qps=1)])
        w.close()
        import os

        files = [f for f in os.listdir(tmp_path) if not f.endswith(".idx")]
        assert 1 <= len(files) <= 3

    def test_metric_timer_collects_from_engine(self, manual_clock):
        with sentinel.entry("timer_res"):
            pass
        manual_clock.sleep(1000)  # move into the next second so prev is complete
        timer = MetricTimer.__new__(MetricTimer)  # no writer needed
        nodes = MetricTimer.collect_once(timer)
        names = [n.resource for n in nodes]
        assert "timer_res" in names


class TestHeartbeat:
    def test_heartbeat_posts_registration(self, command_center):
        # a tiny dashboard stub: reuse the command center HTTP machinery
        received = {}
        from sentinel_tpu.transport.command import command_mapping

        @command_mapping("registry/machine", "test stub")
        def stub(params, body):
            received.update(json.loads(body))
            return "ok"

        from sentinel_tpu.transport.heartbeat import HeartbeatSender

        hb = HeartbeatSender(
            dashboard_addrs=[f"127.0.0.1:{command_center.port}"],
            command_port=1234,
        )
        assert hb.send_once() is True
        assert received["port"] == 1234
        assert received["app"]

class TestSwitchCommands:
    """Regression: sentinel_tpu.local.sph must resolve to the *module*, not the
    re-exported ``sph`` function (round-2 shadowing bug broke these commands
    and reset_for_tests)."""

    def test_get_and_set_switch_roundtrip(self, command_center):
        status, body = http_get(command_center, "getSwitch")
        assert status == 200
        assert json.loads(body)["enabled"] is True

        status, body = http_get(command_center, "setSwitch?value=false")
        assert status == 200 and "success" in body
        status, body = http_get(command_center, "getSwitch")
        assert json.loads(body)["enabled"] is False

        http_get(command_center, "setSwitch?value=true")
        status, body = http_get(command_center, "getSwitch")
        assert json.loads(body)["enabled"] is True

    def test_set_switch_rejects_bad_value(self, command_center):
        status, body = http_get(command_center, "setSwitch?value=banana")
        assert "error" in body

    def test_local_reset_for_tests_direct(self):
        import sentinel_tpu.local as local_pkg

        local_pkg.reset_for_tests()  # must not raise
        from sentinel_tpu.local.sph import is_enabled

        assert is_enabled() is True


class TestDatasourceClusterAssignment:
    """Property/datasource-driven cluster reconfiguration
    (ClusterClientConfigManager / ClusterStateManager property path)."""

    @pytest.fixture(autouse=True)
    def clean(self):
        from sentinel_tpu.cluster import api as cluster_api
        from sentinel_tpu.cluster import assign
        from sentinel_tpu.transport import handlers as H

        yield
        assign.reset_for_tests()
        H.apply_cluster_mode(-1)  # stop any promoted server
        H._CLUSTER_CLIENT_CONFIG.clear()
        cluster_api.reset_for_tests()

    def test_file_assignment_repoints_client(self, tmp_path):
        import jax  # noqa: F401  (conftest pinned CPU)

        from sentinel_tpu.cluster import api as cluster_api
        from sentinel_tpu.cluster import assign
        from sentinel_tpu.cluster.server import TokenServer
        from sentinel_tpu.cluster.token_service import DefaultTokenService
        from sentinel_tpu.datasource.file import FileRefreshableDataSource
        from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
        from sentinel_tpu.engine.rules import ThresholdMode
        from sentinel_tpu.transport import handlers as H

        cfg = EngineConfig(max_flows=16, max_namespaces=4, batch_size=64)
        svc = DefaultTokenService(cfg)
        svc.load_rules(
            [ClusterFlowRule(flow_id=1, count=3.0,
                             mode=ThresholdMode.GLOBAL)]
        )
        server = TokenServer(svc, port=0)
        server.start()
        try:
            path = tmp_path / "assign.json"
            path.write_text(json.dumps(
                {"serverHost": "127.0.0.1", "serverPort": server.port,
                 "requestTimeout": 2000, "namespace": "nsX"}
            ))
            ds = FileRefreshableDataSource(str(path), converter=json.loads)
            assign.register_client_assign_property(ds.property)
            ds.refresh()
            assert H._CLUSTER_CLIENT_CONFIG["serverPort"] == server.port
            assert H._CLUSTER_CLIENT_CONFIG["namespace"] == "nsX"
            assert cluster_api.get_mode() == cluster_api.ClusterMode.CLIENT
            # the installed client really serves verdicts from that server
            oks = sum(
                cluster_api._pick_service().request_token(1).ok
                for _ in range(5)
            )
            assert oks == 3
            # flip the file → client re-points (new port recorded)
            path.write_text(json.dumps(
                {"serverHost": "127.0.0.1", "serverPort": server.port,
                 "requestTimeout": 50, "namespace": "nsY"}
            ))
            ds.refresh()
            assert H._CLUSTER_CLIENT_CONFIG["namespace"] == "nsY"
        finally:
            server.stop()

    def test_mode_property_promotes_and_demotes(self):
        from sentinel_tpu.cluster import api as cluster_api
        from sentinel_tpu.cluster import assign
        from sentinel_tpu.core.property import DynamicProperty

        prop = DynamicProperty()
        assign.register_cluster_mode_property(prop)
        prop.update_value({"mode": 1, "tokenPort": 0})
        assert cluster_api.get_embedded_server() is not None
        assert cluster_api.get_mode() == cluster_api.ClusterMode.SERVER
        prop.update_value(-1)
        assert cluster_api.get_embedded_server() is None

    def test_identical_assignment_does_not_churn_connection(self, tmp_path):
        from sentinel_tpu.cluster import api as cluster_api
        from sentinel_tpu.cluster import assign
        from sentinel_tpu.core.property import DynamicProperty

        prop = DynamicProperty()
        assign.register_client_assign_property(prop)
        payload = {"serverHost": "127.0.0.1", "serverPort": 19999}
        prop.update_value(dict(payload))
        first = cluster_api._client
        assert first is not None
        # same assignment again (datasource poll) → same client object
        prop.update_value({**payload, "_noise": 1})  # dict differs, config same
        assert cluster_api._client is first

    def test_mode_property_port_change_moves_server(self):
        from sentinel_tpu.cluster import api as cluster_api
        from sentinel_tpu.cluster import assign
        from sentinel_tpu.core.property import DynamicProperty
        from sentinel_tpu.transport import handlers as H

        prop = DynamicProperty()
        assign.register_cluster_mode_property(prop)
        prop.update_value({"mode": 1, "tokenPort": 0})
        first = H._EMBEDDED_SERVER["server"]
        port1 = first.port
        # pick a different concrete port and push it
        import socket as s

        sock = s.socket()
        sock.bind(("127.0.0.1", 0))
        port2 = sock.getsockname()[1]
        sock.close()
        prop.update_value({"mode": 1, "tokenPort": port2})
        second = H._EMBEDDED_SERVER["server"]
        assert second.port == port2
        assert second.service is first.service  # rules/counters preserved

    def test_reassignment_after_demotion_restores_client_mode(self):
        from sentinel_tpu.cluster import api as cluster_api
        from sentinel_tpu.transport import handlers as H

        payload = {"serverHost": "127.0.0.1", "serverPort": 19998}
        assert H.apply_client_assignment(payload) is None
        assert cluster_api.get_mode() == cluster_api.ClusterMode.CLIENT
        H.apply_cluster_mode(-1)  # fleet ops switch the agent off
        assert cluster_api.get_mode() == cluster_api.ClusterMode.NOT_STARTED
        # identical re-assignment must restore CLIENT mode, not no-op
        assert H.apply_client_assignment(payload) is None
        assert cluster_api.get_mode() == cluster_api.ClusterMode.CLIENT
        assert cluster_api._pick_service() is not None

    def test_mode_port_move_rolls_back_on_bind_failure(self):
        import socket as s

        from sentinel_tpu.cluster import api as cluster_api
        from sentinel_tpu.engine import ClusterFlowRule
        from sentinel_tpu.engine.rules import ThresholdMode
        from sentinel_tpu.transport import handlers as H

        H.apply_cluster_mode(1, 0)
        server = H._EMBEDDED_SERVER["server"]
        old_port = server.port
        service = server.service
        service.load_rules(
            [ClusterFlowRule(flow_id=9, count=5.0, mode=ThresholdMode.GLOBAL)]
        )
        # a port that is already bound → the move must fail...
        blocker = s.socket()
        blocker.bind(("0.0.0.0", 0))
        blocker.listen(1)
        busy_port = blocker.getsockname()[1]
        try:
            with pytest.raises(Exception):
                H.apply_cluster_mode(1, busy_port)
            # ...and roll back: a server still runs on the old port with the
            # SAME service (rules preserved)
            rolled = H._EMBEDDED_SERVER["server"]
            assert rolled is not None
            assert rolled.port == old_port
            assert rolled.service is service
            assert [r.flow_id for r in rolled.service.current_rules()] == [9]
        finally:
            blocker.close()

    def test_native_transport_selected_by_config(self):
        # csp.sentinel.cluster.server.native=true promotes through the
        # native epoll front door, and a port move preserves the class
        from sentinel_tpu.cluster.server_native import (
            NativeTokenServer,
            native_available,
        )
        from sentinel_tpu.core.config import SentinelConfig
        from sentinel_tpu.transport import handlers as H

        if not native_available():
            pytest.skip("native library not built")
        SentinelConfig.set("csp.sentinel.cluster.server.native", "true")
        try:
            H.apply_cluster_mode(1, 0)
            server = H._EMBEDDED_SERVER["server"]
            assert isinstance(server, NativeTokenServer)
            import socket as s

            sock = s.socket()
            sock.bind(("0.0.0.0", 0))
            new_port = sock.getsockname()[1]
            sock.close()
            H.apply_cluster_mode(1, new_port)
            moved = H._EMBEDDED_SERVER["server"]
            assert isinstance(moved, NativeTokenServer)
            assert moved.port == new_port
        finally:
            H.apply_cluster_mode(-1)
            SentinelConfig.reset_for_tests()

    def test_port_move_preserves_server_tuning(self):
        # a datasource-driven port change rebuilds the TokenServer; operator
        # tuning (batch window, loop count, …) must survive the move instead
        # of resetting to constructor defaults (round-3 advisor finding)
        import socket as s

        from sentinel_tpu.transport import handlers as H

        H.apply_cluster_mode(1, 0)
        server = H._EMBEDDED_SERVER["server"]
        server.batch_window_ms = 0.7
        server.max_batch = 512
        server.inline_below = 16
        server.idle_ttl_s = 123.0
        sock = s.socket()
        sock.bind(("0.0.0.0", 0))
        new_port = sock.getsockname()[1]
        sock.close()
        H.apply_cluster_mode(1, new_port)
        moved = H._EMBEDDED_SERVER["server"]
        assert moved is not server and moved.port == new_port
        assert moved.batch_window_ms == 0.7
        assert moved.max_batch == 512
        assert moved.inline_below == 16
        assert moved.idle_ttl_s == 123.0

    def test_port_move_rearms_concurrent_expiry(self):
        import socket as s

        from sentinel_tpu.cluster.concurrent import ConcurrentFlowRule
        from sentinel_tpu.transport import handlers as H

        H.apply_cluster_mode(1, 0)
        service = H._EMBEDDED_SERVER["server"].service
        service.load_concurrent_rules(
            [ConcurrentFlowRule(flow_id=4, concurrency_level=2)]
        )
        assert service._conc_timer.is_alive()
        sock = s.socket()
        sock.bind(("0.0.0.0", 0))
        new_port = sock.getsockname()[1]
        sock.close()
        H.apply_cluster_mode(1, new_port)
        moved = H._EMBEDDED_SERVER["server"]
        assert moved.port == new_port
        assert moved.service is service
        # stop() closed the expiry timer; the restart must re-arm it
        assert service._conc_timer.is_alive()
