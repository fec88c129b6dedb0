"""cellbench's own tests: run by hand and in the CPU rehearsal,

    python3 -m pytest cellbench/tests -q

on the CPU backend with four virtual devices (set before JAX is loaded)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
