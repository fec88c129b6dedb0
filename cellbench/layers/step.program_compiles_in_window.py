"""Backend compiles (cache hits included) inside the measured window, by the
program's own counter: ``compiles_total`` of ``stage_snapshot()`` after the
window less before it. Reads what ``step.compiles_in_window`` reads from the
harness's listener; 0 in a sound run. None where the program has no such
counter (a tree from before PR 24)."""

NAME = "step.program_compiles_in_window"
UNIT = "count"
LAYER = "decide step"
MOVES = "verdict_latency_p95_ms"
SOURCE = "program_counter"


def reduce(snap):
    a = snap["before"]["stages"].get("compiles_total")
    b = snap["after"]["stages"].get("compiles_total")
    if a is None or b is None:
        return None
    return float(b - a)
