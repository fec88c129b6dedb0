"""The server's own verdict latency, 95th percentile over the frames of the
whole window: last byte in (the ``recv()`` that completed the frame) to last
byte out (``send()`` took the end of its reply), the native door's
``door_residence_ms`` histogram. ``stage_snapshot()`` carries its cumulative
bucket counts, so the window's quantile is read from the difference of two
snapshots, which a ``p95`` since process start cannot give. None where the
program has no such histogram (a tree from before PR 38) or no reply went
out."""

NAME = "door.residence_p95_ms"
UNIT = "ms"
LAYER = "door"
MOVES = "verdict_latency_p95_ms"
SOURCE = "program_counter"


def reduce(snap):
    from cellbench.layers._window import window_quantile

    a = snap["before"]["stages"].get("door_residence_ms")
    b = snap["after"]["stages"].get("door_residence_ms")
    if a is None or b is None or "cum" not in a or "cum" not in b:
        return None
    return window_quantile(a, b, 0.95)
