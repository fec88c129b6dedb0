"""Serve-bench harness units + one live end-to-end round.

The serve bench is round-5's primary evidence instrument (served rate,
load-latency curve, operating point), so its selection logic is tested
like product code; one live closed-loop round through a real front door
keeps the client protocol honest.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import serve_bench  # noqa: E402


class TestOperatingPoint:
    def _pt(self, rate, p99, sent=1000, dropped=0, lost=0):
        return {
            "offered_rate": rate, "achieved_rate": rate,
            "frames_sent": sent, "frames_dropped": dropped,
            "frames_lost": lost, "p99_ms": p99,
        }

    def test_highest_rate_meeting_slo_wins(self):
        pts = [self._pt(100, 0.5), self._pt(200, 1.0), self._pt(400, 1.9),
               self._pt(800, 5.0)]
        assert serve_bench.operating_point(pts)["achieved_rate"] == 400

    def test_shedding_point_excluded(self):
        # fast p99 but >1% frames shed: the latency is survivorship bias
        pts = [self._pt(100, 0.5),
               self._pt(800, 0.9, sent=900, dropped=100)]
        assert serve_bench.operating_point(pts)["achieved_rate"] == 100

    def test_no_point_meets_slo(self):
        pts = [self._pt(100, 3.0), self._pt(200, 8.0)]
        assert serve_bench.operating_point(pts) is None

    def test_missing_p99_skipped(self):
        pts = [{"offered_rate": 1, "error": "clients failed"},
               self._pt(50, 1.0)]
        assert serve_bench.operating_point(pts)["achieved_rate"] == 50


class TestPercentiles:
    def test_pcts_empty(self):
        out = serve_bench._pcts(np.empty(0))
        assert out["p99_ms"] is None and out["max_ms"] is None

    def test_pcts_values(self):
        out = serve_bench._pcts(np.asarray([1.0, 2.0, 3.0, 100.0]))
        assert out["p50_ms"] == 2.5 and out["max_ms"] == 100.0


class TestClientPacing:
    """Open-loop sender math from serve_client (absolute schedule)."""

    def test_open_loop_offered_rate_is_absolute_schedule(self):
        import serve_client

        dt, n_frames = serve_client.open_loop_schedule(512, 100_000.0, 2.0)
        assert dt == pytest.approx(0.00512)
        assert n_frames == 390
        # the realized offered load over the window matches the nominal
        # rate (the schedule spans `seconds` exactly, jitter-independent)
        assert n_frames * 512 / 2.0 == pytest.approx(100_000.0, rel=0.01)
        # degenerate input still sends at least one frame
        assert serve_client.open_loop_schedule(1024, 10.0, 0.1)[1] == 1


class TestServeLive:
    def test_closed_loop_round_through_native_door(self):
        """One real client subprocess against a real front door: the
        served count, error count, and RTT samples must be coherent."""
        from sentinel_tpu.cluster.server_native import native_available

        service, server, front_door = serve_bench.build_server(
            n_flows=256, max_batch=1024, serve_buckets=(256, 1024),
            native=native_available(),
        )
        try:
            out = serve_bench.run_closed(
                server.port, clients=1, batch=128, pipeline=2,
                seconds=1.0, n_flows=256,
            )
            assert out["errors"] == 0
            assert out["verdicts_ok"] > 0
            assert out["verdicts_ok"] % 128 == 0  # whole frames only
            assert out["p99_ms"] is not None and out["p99_ms"] > 0
        finally:
            server.stop()
            service.close()

    def test_warmup_latency_excluded_from_closed_loop_window(self):
        """A slow FIRST response (server-side compile) must not consume
        the measurement window: each pump thread's clock starts after its
        warmup round trip. Regression: a remote-compile warmup once ate
        the whole window and produced a 0-verdict, 0-error artifact."""
        import socket
        import threading
        import time as _time

        from sentinel_tpu.cluster import protocol as P

        delay_s = 1.2
        seconds = 0.8
        srv = socket.create_server(("127.0.0.1", 0))
        port = srv.getsockname()[1]
        stop = threading.Event()

        def serve():
            srv.settimeout(0.2)
            conns = []
            while not stop.is_set():
                try:
                    conn, _ = srv.accept()
                except socket.timeout:
                    continue
                t = threading.Thread(
                    target=handle, args=(conn,), daemon=True
                )
                t.start()
                conns.append(t)

        def handle(conn):
            frames = P.FrameReader()
            first = True
            try:
                while not stop.is_set():
                    data = conn.recv(65536)
                    if not data:
                        return
                    for payload in frames.feed(data):
                        xid, ids, _c, _p = P.decode_batch_request(payload)
                        if first:
                            _time.sleep(delay_s)  # simulated cold compile
                            first = False
                        n = len(ids)
                        conn.sendall(P.encode_batch_response(
                            xid, np.zeros(n, np.int8),
                            np.zeros(n, np.int32), np.zeros(n, np.int32),
                        ))
            except OSError:
                return

        st = threading.Thread(target=serve, daemon=True)
        st.start()
        try:
            out = serve_bench.run_closed(
                port, clients=1, batch=64, pipeline=2,
                seconds=seconds, n_flows=64,
            )
            # the old clock placement yielded 0 verdicts here (delay_s >
            # seconds); the fixed clock measures a full post-warmup window
            assert out["verdicts_ok"] > 0
            assert out["errors"] == 0
            assert out["p99_ms"] is not None
        finally:
            stop.set()
            srv.close()

    def test_client_subprocess_never_claims_accelerator(self):
        """One process per chip: the client module pins jax to CPU itself,
        before anything else imports it, whatever environment started it.
        JAX_PLATFORMS is deliberately NOT overridden here — the assertion
        below reads the config value the module itself must have pinned."""
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.pop("JAX_PLATFORMS", None)
        src = (
            "import sys; sys.argv=['x']; "
            "import importlib.util as u; "
            f"spec=u.spec_from_file_location('sc', r'{serve_bench.CLIENT}'); "
            "m=u.module_from_spec(spec); spec.loader.exec_module(m); "
            "import jax; print(jax.config.jax_platforms)"
        )
        proc = subprocess.run(
            [sys.executable, "-c", src], capture_output=True, text=True,
            timeout=120, env=env,
        )
        # module-level code must have pinned the platform config to cpu
        # (main() isn't run: argv has no --port, __name__ != '__main__');
        # reading jax.config initializes no backend
        assert proc.returncode == 0, proc.stderr[-500:]
        assert proc.stdout.strip().splitlines()[-1] == "cpu"
