"""Share of the window's materializations that found the dispatch's verdict
buffer ready on entry: the device had finished before the reply lane asked,
so what ``device_wait_ms`` then holds is the copy started at launch and the
GIL, not the device. ``verdict_copy_ready_total`` over the ``device_wait_ms``
count, after the window less before it. None where the program has no such
counter (a tree from before PR 25)."""

NAME = "service.verdict_copy_ready_share"
UNIT = "%"
LAYER = "service"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if ("verdict_copy_ready_total" not in stages
                or "device_wait_ms" not in stages):
            return None
    n = b["device_wait_ms"]["count"] - a["device_wait_ms"]["count"]
    if n <= 0:
        return None
    ready = b["verdict_copy_ready_total"] - a["verdict_copy_ready_total"]
    return 100.0 * ready / n
