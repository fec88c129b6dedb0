"""The rules of the chip path, checked where they can be on the CPU.

- ``chip_smoke.py`` refuses to run without a TPU and says what it found;
- the compile cache is placed from outside or at one fixed path;
- a Pallas kernel asked for by name is compiled or raises — the "auto"
  probe reports a refused kernel with the compiler's message;
- the native library is rebuilt when stale, and a door that cannot have it
  raises with the compiler's output.
"""

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_the_cpu():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert "CpuDevice" in proc.stdout  # names the device it found
    assert '"ok"' not in proc.stdout  # and prints no result
    assert time.monotonic() - t0 < 60


class TestCompileCache:
    def test_placed_from_outside_is_left_alone(self, monkeypatch, tmp_path):
        from sentinel_tpu.core.compile_cache import ensure_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        updates = []
        monkeypatch.setattr(
            jax.config, "update", lambda *a: updates.append(a)
        )
        assert ensure_compile_cache() == str(tmp_path)
        assert updates == []

    def test_unplaced_goes_to_the_checkout(self, monkeypatch):
        from sentinel_tpu.core.compile_cache import ensure_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        updates = []
        monkeypatch.setattr(
            jax.config, "update", lambda *a: updates.append(a)
        )
        want = os.path.join(REPO, ".jax_cache")
        assert ensure_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]

    def test_one_helper_in_the_tree(self):
        """No second copy of the cache setup in the program."""
        hits = []
        for top in ("sentinel_tpu", "benchmarks", "examples"):
            for root, _, files in os.walk(os.path.join(REPO, top)):
                hits += [os.path.join(root, f) for f in files
                         if f.endswith(".py")]
        hits += [os.path.join(REPO, f) for f in os.listdir(REPO)
                 if f.endswith(".py")]
        setters = [
            os.path.relpath(f, REPO) for f in hits
            if "jax_compilation_cache_dir" in open(f).read()
        ]
        assert setters == ["sentinel_tpu/core/compile_cache.py"]


class TestKernelsCompileOrRaise:
    """On the CPU Mosaic cannot build anything, which makes this backend a
    faithful stand-in for "the compiler refuses the kernel"."""

    def _param_args(self, cfg, n=8):
        return (
            jnp.zeros(n, jnp.int32), jnp.zeros((n, cfg.depth), jnp.int32),
            jnp.ones(n, jnp.int32), jnp.full(n, 9.0, jnp.float32),
            jnp.ones(n, bool), jnp.int32(0),
        )

    def test_forced_param_pallas_raises(self):
        from sentinel_tpu.engine import param as P

        P._param_decide_pallas.clear_cache()
        cfg = P.ParamConfig(max_param_rules=4, width=128, impl="pallas")
        with pytest.raises(ValueError, match="interpret"):
            P.param_decide(cfg, P.make_param_state(cfg),
                           *self._param_args(cfg))

    def test_probe_reports_the_losers_reason(self):
        import logging

        from sentinel_tpu.core.log import record_log
        from sentinel_tpu.engine import param as P

        logged = []
        handler = logging.Handler()
        handler.emit = lambda record: logged.append(record.getMessage())
        record_log.addHandler(handler)
        P._param_decide_pallas.clear_cache()
        try:
            choice, reason = P._probe_param_impl(
                P.ParamConfig(max_param_rules=8, width=128), 64
            )
        finally:
            record_log.removeHandler(handler)
        assert choice == "jax"
        assert "jax " in reason and "ms/step" in reason  # the winner's time
        assert "cms 8x2x2x128, 64 rows" in reason  # the geometry it timed
        assert "pallas refused: ValueError" in reason
        assert "interpret mode" in reason  # the compiler's own words
        assert any("refused by the compiler" in m for m in logged)

    def test_auto_on_a_tpu_carries_the_reason(self, monkeypatch):
        from sentinel_tpu.engine import param as P

        monkeypatch.delenv("SENTINEL_PARAM_IMPL", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(P, "_AUTO_IMPL", {})
        probed = []

        def probe(config, rows):
            probed.append((config.sketch, config.width, rows))
            return "jax", f"probed {config.sketch} {config.width} {rows}"

        monkeypatch.setattr(P, "_probe_param_impl", probe)
        assert P.explain_param_impl("auto", "salsa") == (
            "jax", "probed salsa 2048 64"
        )
        assert P.resolve_param_impl("auto", "salsa") == "jax"
        # a service names its own geometry and largest serve bucket: timed
        # once for that geometry, and what a caller without one then gets
        cfg = P.ParamConfig(width=16384, depth=4, sketch="salsa")
        for _ in range(2):
            assert P.explain_param_impl("auto", "salsa", cfg, 4096) == (
                "jax", "probed salsa 16384 4096"
            )
        assert P.explain_param_impl("auto", "salsa")[1] == (
            "probed salsa 16384 4096"
        )
        assert probed == [("salsa", 2048, 64), ("salsa", 16384, 4096)]


class TestNativeLoader:
    def _fresh(self, monkeypatch, so_path):
        from sentinel_tpu.native import lib

        monkeypatch.setattr(lib, "_lib", None)
        monkeypatch.setattr(lib, "_load_failed", False)
        monkeypatch.setattr(lib, "_load_error", "")
        monkeypatch.setattr(lib, "_SO_PATH", str(so_path))
        monkeypatch.delenv("SENTINEL_NATIVE_SO", raising=False)
        monkeypatch.delenv("SENTINEL_NATIVE_AUTOBUILD", raising=False)
        return lib

    def test_stale_when_a_source_is_newer(self, monkeypatch, tmp_path):
        from sentinel_tpu.native import build

        so, src = tmp_path / "lib.so", tmp_path / "a.cpp"
        lib = self._fresh(monkeypatch, so)
        monkeypatch.setattr(build, "SOURCES", [str(src)])
        src.write_text("// v1")
        assert lib._stale()  # no library yet
        so.write_bytes(b"")
        os.utime(src, (1_000, 1_000))
        os.utime(so, (2_000, 2_000))
        assert not lib._stale()
        os.utime(src, (3_000, 3_000))  # the source moved on
        assert lib._stale()

    def test_door_error_carries_the_compiler_output(self, monkeypatch,
                                                    tmp_path):
        from sentinel_tpu.native import build

        lib = self._fresh(monkeypatch, tmp_path / "missing.so")

        def failing_build(verbose=True):
            raise subprocess.CalledProcessError(
                1, ["g++", "x.cpp"], stderr="x.cpp:7: error: expected ';'"
            )

        monkeypatch.setattr(build, "build", failing_build)
        assert lib.load() is None  # optional accelerations degrade…
        with pytest.raises(RuntimeError) as e:  # …a door by name does not
            lib.Frontdoor("127.0.0.1", 0)
        assert "g++ x.cpp exited 1" in str(e.value)
        assert "expected ';'" in str(e.value)
