"""cellbench: the cell benchmark of sentinel-tpu (see cellbench/README.md).

Everything the yardstick needs lives here: traffic generation, the wire
codec the generators speak, the failure accounting, the plain reference,
the trace reduction, the table of peaks and the per-layer readers. From the
program it takes the system under test, its metrics snapshot, its flight
recorder and the JAX profiler's trace.
"""
