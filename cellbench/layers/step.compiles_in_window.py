"""Backend compiles (cache hits included) that ended inside the measured
window, from a jax.monitoring listener the harness registers. Always 0 in a
sound run; a run that compiled names the jit on an earlier line."""

NAME = "step.compiles_in_window"
UNIT = "count"
LAYER = "decide step"
MOVES = "verdict_latency_p95_ms"
SOURCE = "program_counter"


def reduce(snap):
    return float(len(snap["compiles_in_window"]))
