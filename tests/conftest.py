"""Test config: force an 8-device virtual CPU mesh.

Multi-chip sharding is tested on CPU via
``--xla_force_host_platform_device_count`` (SURVEY.md §4); the real-TPU path is
exercised by ``chip_smoke.py`` on the chip.

The suite pins itself to the CPU (the ``jax.config.update`` below, before
any backend exists) whatever ``JAX_PLATFORMS`` says, and refuses to start on
anything else: on a machine with a chip it would otherwise take the chip —
one process per chip — and run for minutes against it.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from sentinel_tpu.core import clock as clock_mod  # noqa: E402
from sentinel_tpu.core.clock import ManualClock  # noqa: E402


def pytest_sessionstart(session):
    # Fail fast if the suite is about to run on real hardware.
    assert jax.devices()[0].platform == "cpu", (
        "test suite must run on the virtual CPU mesh, got: %s" % jax.devices()
    )


def pytest_sessionfinish(session, exitstatus):
    # Backstop: a test that provisioned the embedded token server via
    # setClusterMode but died before its cleanup must not leave a port-bound
    # server logging past the pytest summary.
    try:
        from sentinel_tpu.transport.handlers import (
            _EMBEDDED_LOCK,
            _EMBEDDED_SERVER,
        )

        with _EMBEDDED_LOCK:
            srv, _EMBEDDED_SERVER["server"] = _EMBEDDED_SERVER["server"], None
        if srv is not None:
            srv.stop()
    except Exception:
        pass


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Run config-selected Pallas kernels under the Pallas interpreter.

    Nothing in the package derives ``interpret=`` from the backend (a chip
    process compiles the kernel or raises), so a CPU parity test that drives
    ``ParamConfig(impl="pallas")`` or ``prefix_impl="pallas"`` asks for the
    interpreter itself, here: every ``pl.pallas_call`` built while the
    fixture is active gets ``interpret=True``. (JAX's own
    ``pltpu.force_tpu_interpret_mode()`` would do, but its TPU simulator
    runs these suites ~2.7x slower.)"""
    from jax.experimental import pallas as pl

    compiled_call = pl.pallas_call

    def interpreted_call(*args, **kwargs):
        kwargs["interpret"] = True
        return compiled_call(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", interpreted_call)


@pytest.fixture
def manual_clock():
    """Install a deterministic clock for the duration of a test."""
    mc = ManualClock()
    prev = clock_mod.set_clock(mc)
    yield mc
    clock_mod.set_clock(prev)
