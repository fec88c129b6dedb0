"""How often the device lane's dispatch is of another kind than the one
before (flow rows, hot-parameter rows of one count of values, concurrency
rows): ``lane_kind_switches_total`` after the window less before it, over the
window's seconds. Where rules of two kinds share a server every switch ends
a fusion early. None where the program has no such counter (a tree from
before PR 49)."""

NAME = "lane.kind_switches_per_s"
UNIT = "1/s"
LAYER = "device lane"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    from cellbench.layers import _lane

    d = _lane.grew(snap, ["lane_kind_switches_total"])
    if d is None or snap["seconds"] <= 0:
        return None
    return d[0] / snap["seconds"]
